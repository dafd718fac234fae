"""The LFM2 mixture-of-experts family (``model_type`` ``lfm2_moe``): what a
driver takes from this architecture, as ``families/dense/__init__.py``
lists it.

The decoder of ``ray_tpu/models/transformer.py`` as a layer pattern: gated
short-convolution layers beside grouped-query attention layers (RMSNorm on
each head of q and k before rope), leading dense SwiGLU layers, then
experts routed as published (sigmoid scores, top-k over scores plus a
bias, renormalised gates, no token dropped), a head tied to the table. A
cell of this family is one chip of a share (``deployment.chips_per_layer``):
``model["num_experts"]`` counts the experts held, the router's published
width and which experts are held come as ``assumed`` entries
(``router_experts``, ``experts_held``) through ``harness.run_model``.

Besides the dense family's functions: ``experts_train_cost`` (the grouped
expert products' roofline) and ``moe_load`` (the program's own counter of
tokens per held expert, at the weights and first batch of the seed the
window ran).
"""

from __future__ import annotations

from perfbench import harness
from perfbench import step as train_step

from . import counts, reference, weights

batch_of = weights.batch_of
leaf_norms = weights.leaf_norms
change_norms = weights.change_norms
first_moment = train_step.first_moment
reference_loss = reference.loss
train_flops_per_token = counts.train_flops_per_token
flash_train_cost = counts.flash_train_cost
experts_train_cost = counts.experts_train_cost
total_params = counts.total_params

# The seed of the last tree made on the device: the window's. A reader's
# context does not carry the seed, and ``moe_load`` needs it.
_made = {}


def make_params(model: dict, seed: int) -> dict:
    import jax

    model_config(model)     # a program without the pattern stops here, soon
    params = weights.make_params(model, seed)
    if not isinstance(params["embed"], jax.core.Tracer):   # not a shape
        _made["seed"] = seed
    return params


def model_config(model: dict):
    """The program's configuration of ``model`` as it is run."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    held = tuple(model["experts_held"])
    if len(held) != model["num_experts"]:
        raise SystemExit(f"perfbench lfm2: experts_held {held} is not the "
                         f"{model['num_experts']} experts the model holds")
    try:
        return TransformerConfig(
            vocab_size=model["vocab_size"], d_model=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=counts.head_dim(model),
            d_ff=model["intermediate_size"],
            max_seq_len=model["max_position_embeddings"],
            rope_theta=float(model["rope_parameters"]["rope_theta"]),
            norm_eps=model["norm_eps"], qk_norm=True,
            tie_embeddings=model["tie_word_embeddings"],
            layer_types=tuple(counts.OPERATORS[t]
                              for t in model["layer_types"]),
            conv_kernel=model["conv_L_cache"],
            num_dense_layers=model["num_dense_layers"],
            router_experts=model["router_experts"], experts_held=held,
            experts_per_token=model["num_experts_per_tok"],
            moe_d_ff=model["moe_intermediate_size"], router_score="sigmoid",
            norm_topk=model["norm_topk_prob"],
            routed_scale=float(model["routed_scaling_factor"]),
            expert_bias=model["use_expert_bias"], dtype=jnp.bfloat16)
    except TypeError as exc:
        raise SystemExit(f"perfbench lfm2: this program's TransformerConfig "
                         f"takes no layer pattern ({exc})")


def build_step(config: dict):
    """The AdamW step of the program's ``loss_fn`` on this family's
    batches: (step, init) of ``perfbench/step.py``."""
    from ray_tpu.models import loss_fn

    cfg = model_config(harness.run_model(config))
    hp = config["step"]
    return train_step.adamw_step(
        lambda params, tokens, targets: loss_fn(cfg, params, tokens, targets),
        lambda key, index: batch_of(key, index, hp["batch"], hp["seq_len"],
                                    cfg.vocab_size),
        hp)


def moe_load(model: dict, hp: dict):
    """``{kind: [layers, experts held]}`` of the program's ``moe_load`` at
    the window's seed: its weights made again and its first batch, one
    forward pass. None where no tree was made yet or the program has no
    such counter."""
    import jax

    try:
        from ray_tpu.models.transformer import moe_load as program_load
    except ImportError:
        return None
    if "seed" not in _made:
        return None
    cfg = model_config(model)
    tokens, _ = batch_of(harness.seed_key(_made["seed"]), 0, hp["batch"],
                         hp["seq_len"], cfg.vocab_size)
    params = weights.make_params(model, _made["seed"])
    return jax.device_get(
        jax.jit(lambda p, t: program_load(cfg, p, t))(params, tokens))
