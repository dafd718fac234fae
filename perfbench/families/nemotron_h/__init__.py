"""The Nemotron-H family (NVIDIA's Mamba-2 / attention / latent-expert
hybrids; the catalog's ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``): what a
driver takes from this architecture, as ``families/dense/__init__.py``
lists it.

The decoder of ``ray_tpu/models/transformer.py`` as a layer pattern whose
layers are each a mixer or a feed-forward alone
(``hybrid_override_pattern``): Mamba-2 layers (``M``: a selective
state-space scan in chunks, ``ops/ssd.py``), attention layers without a
rotary embedding (``*``) and expert layers (``E``: sigmoid scores, a
selection bias, renormalised gates times a scale, no token dropped; the
routed experts two matrices with a squared ReLU between, at a latent width
between a down and an up projection every token passes, and a shared
expert at the model's width); an untied head. A cell of this family is one
chip of a share (``deployment.chips_per_layer``): ``n_routed_experts``,
``mamba_num_heads``, ``n_groups``, ``num_attention_heads`` and
``num_key_value_heads`` count what is held here; the router's published
width and which experts are held come as ``assumed`` entries
(``router_experts``, ``experts_held``) through ``harness.run_model``.

Besides the dense family's functions: ``ssd_train_cost`` (the selective
scan's roofline), ``experts_train_cost`` (the grouped products') and
``moe_load`` (the program's own counter of tokens per held expert, at the
weights and first batch of the seed the window ran); ``segment_ms`` and
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
ssd_train_cost = counts.ssd_train_cost
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
    if len(held) != model["n_routed_experts"]:
        raise SystemExit(f"perfbench nemotron_h: experts_held {held} is not "
                         f"the {model['n_routed_experts']} experts the model "
                         f"holds")
    kinds = [k.split("_") for k in counts.kinds(model)]  # operator, ffn
    try:
        return TransformerConfig(
            vocab_size=model["vocab_size"], d_model=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            max_seq_len=model["max_position_embeddings"],
            norm_eps=model["layer_norm_epsilon"],
            tie_embeddings=model["tie_word_embeddings"],
            layer_types=tuple(op for op, _ffn in kinds),
            layer_ffns=tuple(ffn for _op, ffn in kinds),
            rope=model["attention_rope"],
            conv_kernel=model["conv_kernel"],
            mamba_heads=model["mamba_num_heads"],
            mamba_head_dim=model["mamba_head_dim"],
            mamba_state=model["ssm_state_size"],
            mamba_groups=model["n_groups"], mamba_chunk=model["chunk_size"],
            router_experts=model["router_experts"], experts_held=held,
            experts_per_token=model["num_experts_per_tok"],
            moe_d_ff=model["moe_intermediate_size"],
            moe_latent=model["moe_latent_size"],
            ffn_act=model["mlp_hidden_act"], router_score="sigmoid",
            norm_topk=model["norm_topk_prob"],
            routed_scale=float(model["routed_scaling_factor"]),
            expert_bias=True, router_groups=model["n_group"],
            router_groups_kept=model["topk_group"],
            shared_d_ff=model["moe_shared_expert_intermediate_size"],
            dtype=jnp.bfloat16)
    except (TypeError, ValueError) as exc:
        raise SystemExit(f"perfbench nemotron_h: this program's "
                         f"TransformerConfig takes no Mamba layer, no layer "
                         f"that is a mixer or a feed-forward alone, or no "
                         f"latent expert ({exc})")


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
    forward pass, kept for the readers that ask. None where no tree was
    made yet."""
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
