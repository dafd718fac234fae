"""The Ling 3.0 language-model family (inclusionAI's linear-hybrid
mixture of experts; the catalog's ``Ling-3.0-flash-VL`` without its vision
tower): what a driver takes from this architecture, as
``families/dense/__init__.py`` lists it.

The decoder of ``ray_tpu/models/transformer.py`` as a layer pattern: Kimi
Delta Attention layers (``kda``: short causal taps, per-channel decay, the
delta rule) beside latent-attention layers (``mla``: one low-rank latent
for keys and values, a rotary key part the heads share, values narrower
than keys, a gate a head), five to one; leading dense SwiGLU layers, then
experts routed as published (sigmoid scores, a selection bias, the choice
limited to the best groups of experts, renormalised gates times a scale,
no token dropped) with a shared expert beside them; an untied head. A cell
of this family is one chip of a share (``deployment.chips_per_layer``):
``model["num_experts"]`` and ``model["num_attention_heads"]`` count what is
held here; the router's published width, which experts are held and each
layer's operator come as ``assumed`` entries (``router_experts``,
``experts_held``, ``layer_types``) through ``harness.run_model``.

Besides the dense family's functions: ``kda_train_cost`` (the recurrence's
roofline), ``experts_train_cost`` (the grouped products') and ``moe_load``
(the program's own counter of tokens per held expert, at the weights and
first batch of the seed the window ran); ``segment_ms`` and
``unattributed_share`` (``join.py``: the device time by segment as this
family's readers count it).
"""

from __future__ import annotations

from perfbench import harness
from perfbench import step as train_step

from . import counts, join, reference, weights

batch_of = weights.batch_of
leaf_norms = weights.leaf_norms
change_norms = weights.change_norms
first_moment = train_step.first_moment
reference_loss = reference.loss
train_flops_per_token = counts.train_flops_per_token
flash_train_cost = counts.flash_train_cost
kda_train_cost = counts.kda_train_cost
experts_train_cost = counts.experts_train_cost
total_params = counts.total_params
segment_ms = join.segment_ms
unattributed_share = join.unattributed_share

# The seed of the last tree made on the device: the window's. A reader's
# context does not carry the seed, and ``moe_load`` needs it; the load it
# counted there is kept beside it.
_made = {}


def make_params(model: dict, seed: int) -> dict:
    import jax

    model_config(model)     # a program without these layers stops here, soon
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
        raise SystemExit(f"perfbench ling3: experts_held {held} is not the "
                         f"{model['num_experts']} experts the model holds")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise SystemExit("perfbench ling3: latent attention gives every "
                         "query head keys and values of its own")
    try:
        return TransformerConfig(
            vocab_size=model["vocab_size"], d_model=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], d_ff=model["intermediate_size"],
            max_seq_len=model["max_position_embeddings"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=model["rms_norm_eps"],
            tie_embeddings=model["tie_word_embeddings"],
            layer_types=tuple(model["layer_types"]),
            conv_kernel=model["short_conv_kernel_size"],
            kda_gate_floor=float(model["kda_lower_bound"]),
            kv_lora_rank=model["kv_lora_rank"],
            qk_nope_dim=model["qk_nope_head_dim"],
            qk_rope_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"],
            num_dense_layers=model["first_k_dense_replace"],
            router_experts=model["router_experts"], experts_held=held,
            experts_per_token=model["num_experts_per_tok"],
            moe_d_ff=model["moe_intermediate_size"],
            router_score=model["score_function"],
            norm_topk=model["norm_topk_prob"],
            routed_scale=float(model["routed_scaling_factor"]),
            expert_bias=model["moe_router_enable_expert_bias"],
            router_groups=model["n_group"],
            router_groups_kept=model["topk_group"],
            shared_d_ff=model["moe_shared_expert_intermediate_size"],
            dtype=jnp.bfloat16)
    except (TypeError, ValueError) as exc:
        raise SystemExit(f"perfbench ling3: this program's TransformerConfig "
                         f"takes no KDA or MLA layer ({exc})")


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
    forward pass, kept for the three readers that ask. None where no tree
    was made yet."""
    import jax

    from ray_tpu.models.transformer import moe_load as program_load

    if "seed" not in _made:
        return None
    if _made.get("load_of") != _made["seed"]:
        cfg = model_config(model)
        tokens, _ = batch_of(harness.seed_key(_made["seed"]), 0, hp["batch"],
                             hp["seq_len"], cfg.vocab_size)
        params = weights.make_params(model, _made["seed"])
        _made["load"] = jax.device_get(
            jax.jit(lambda p, t: program_load(cfg, p, t))(params, tokens))
        _made["load_of"] = _made["seed"]
    return _made["load"]
