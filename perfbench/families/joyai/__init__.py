"""The JoyAI-LLM-Flash family (jdopensource's 48B-A2.7B mixture of experts,
``model_type`` ``joyai_llm_flash``; every key of its config is
DeepSeek-V3's): what a driver takes from this architecture, as
``families/dense/__init__.py`` lists it.

The decoder of ``ray_tpu/models/transformer.py`` as a layer pattern of one
operator: latent attention in every layer (``mla``: a query latent with a
norm of its own, one low-rank latent for keys and values, a rotary key part
the heads share, values narrower than keys, no gate), one leading dense
SwiGLU, then experts routed as published (sigmoid scores, a selection bias,
no group limit, renormalised gates times a scale, no token dropped) with a
shared expert beside them; an untied head; and behind the stack one
multi-token-prediction module (``num_nextn_predict_layers``), whose loss
the step adds at ``assumed.mtp_loss_weight``. A cell of this family is one
chip of a share (``deployment.chips_per_layer``): ``n_routed_experts``
counts what is held here; the router's published width and which experts
are held come as ``assumed`` entries (``router_experts``,
``experts_held``) through ``harness.run_model``.

Besides the dense family's functions: ``reference_losses`` (the two losses
apart, as the program's ``loss_parts`` gives its own), ``experts_train_cost`` (the grouped products' roofline) and
``moe_load`` (the program's own counter of tokens per held expert, the
module's router among them, at the weights and first batch of the seed the
window ran); ``segment_ms`` and ``unattributed_share`` (``join.py``: the
device time by segment as this family's readers count it).
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
reference_losses = reference.losses
train_flops_per_token = counts.train_flops_per_token
flash_train_cost = counts.flash_train_cost
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
        raise SystemExit(f"perfbench joyai: experts_held {held} is not the "
                         f"{model['n_routed_experts']} experts the model "
                         f"holds")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise SystemExit("perfbench joyai: latent attention gives every "
                         "query head keys and values of its own")
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise SystemExit("perfbench joyai: the family's reference knows no "
                         "group limit (n_group 1)")
    width = model["moe_intermediate_size"]
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
            q_lora_rank=model["q_lora_rank"], mla_gate=False,
            kv_lora_rank=model["kv_lora_rank"],
            qk_nope_dim=model["qk_nope_head_dim"],
            qk_rope_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"],
            num_dense_layers=model["first_k_dense_replace"],
            router_experts=model["router_experts"], experts_held=held,
            experts_per_token=model["num_experts_per_tok"],
            moe_d_ff=width, router_score=model["scoring_func"],
            norm_topk=model["norm_topk_prob"],
            routed_scale=float(model["routed_scaling_factor"]),
            expert_bias=model["topk_method"] == "noaux_tc",
            shared_d_ff=model["n_shared_experts"] * width,
            mtp_depth=model["num_nextn_predict_layers"],
            mtp_weight=float(model["mtp_loss_weight"]),
            dtype=jnp.bfloat16)
    except (TypeError, ValueError) as exc:
        raise SystemExit(f"perfbench joyai: this program's TransformerConfig "
                         f"takes no query latent or no multi-token-"
                         f"prediction module ({exc})")


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
    the window's seed (the module's router under ``mtp``): its weights made
    again and its first batch, one forward pass, kept for the readers that
    ask. None where no tree was made yet."""
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
