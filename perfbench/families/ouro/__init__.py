"""The Ouro family (ByteDance's LoopLM, ``model_type`` ``ouro``;
arXiv:2510.25741): what a driver takes from this architecture, as
``families/dense/__init__.py`` lists it.

The decoder of ``ray_tpu/models/transformer.py`` as a looped stack: the
layers (multi-head attention with rotary embeddings and SwiGLU, each
sub-layer with an RMSNorm before and after it) run ``total_ut_steps`` times
a step over their own output with the same weights, the final norm after
each pass; an exit gate and the untied head read every pass, and the loss
weighs the passes' next-token losses by the exit distribution the gates
give, with an entropy term (``assumed.exit_entropy_beta``). A cell of this
family is one stage of a pipeline (``deployment.stages``) that every token
passes through ``total_ut_steps`` times, with the table and the head.

Besides the dense family's functions: ``exit_weights`` (the program's own
mean exit distribution over the passes, at the weights and first batch of
the seed the window ran) and ``recompute_ms`` (``join.py``: the device time
the checkpoints' recomputation takes).
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
total_params = counts.total_params
recompute_ms = join.recompute_ms

# The seed of the last tree made on the device: the window's. A reader's
# context does not carry the seed, and ``exit_weights`` needs it; the
# distribution it read there is kept beside it.
_made = {}


def make_params(model: dict, seed: int) -> dict:
    import jax

    model_config(model)     # a program without a looped stack stops here
    params = weights.make_params(model, seed)
    if not isinstance(params["embed"], jax.core.Tracer):   # not a shape
        _made["seed"] = seed
    return params


def model_config(model: dict):
    """The program's configuration of ``model`` as it is run."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    if set(model["layer_types"]) != {"full_attention"} or \
            model["use_sliding_window"] or model["sliding_window"]:
        raise SystemExit("perfbench ouro: the family runs full attention in "
                         "every layer, no sliding window")
    # ``layer_types`` lists the source's layers; a stage runs the first
    # ``num_hidden_layers`` of them
    if len(model["layer_types"]) < model["num_hidden_layers"]:
        raise SystemExit("perfbench ouro: layer_types lists fewer layers "
                         "than num_hidden_layers")
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
            total_ut_steps=model["total_ut_steps"],
            exit_entropy_beta=float(model["exit_entropy_beta"]),
            sandwich_norm=model["sandwich_norm"], dtype=jnp.bfloat16)
    except (TypeError, ValueError) as exc:
        raise SystemExit(f"perfbench ouro: this program's TransformerConfig "
                         f"takes no looped stack or no sandwich norms "
                         f"({exc})")


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


def exit_weights(model: dict, hp: dict):
    """The program's ``exit_weights`` (the exit distribution over the
    passes, averaged over the positions) at the window's seed: its weights
    made again and its first batch, one forward pass, kept for the readers
    that ask. None where no tree was made yet."""
    import jax

    from ray_tpu.models.transformer import exit_weights as program_weights

    if "seed" not in _made:
        return None
    if _made.get("weights_of") != _made["seed"]:
        cfg = model_config(model)
        tokens, _ = batch_of(harness.seed_key(_made["seed"]), 0, hp["batch"],
                             hp["seq_len"], cfg.vocab_size)
        params = weights.make_params(model, _made["seed"])
        _made["weights"] = jax.device_get(
            jax.jit(lambda p, t: program_weights(cfg, p, t))(params, tokens))
        _made["weights_of"] = _made["seed"]
    return _made["weights"]
