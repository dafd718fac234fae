"""Model zoo for the TPU-native framework.

The reference ships model code through RLlib modules and Train integrations
(torch); here the flagship is a jax-native decoder-only transformer wired
directly into the parallelism layer (dp/pp/tp/sp/ep over one Mesh).
"""

from ray_tpu.models.draft import draft_config, shift_params
from ray_tpu.models.transformer import (
    TransformerConfig,
    decode_step,
    exit_weights,
    forward,
    init_kv_cache,
    init_params,
    loss_fn,
    loss_parts,
    make_spmd_train_step,
    param_specs,
    prefill_chunk,
    verify_step,
)

__all__ = [
    "TransformerConfig",
    "decode_step",
    "draft_config",
    "exit_weights",
    "forward",
    "init_kv_cache",
    "init_params",
    "loss_fn",
    "loss_parts",
    "make_spmd_train_step",
    "param_specs",
    "prefill_chunk",
    "shift_params",
    "verify_step",
]
