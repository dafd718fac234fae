"""Fused elementwise/normalization kernels.

XLA fuses most elementwise chains into adjacent matmuls on its own; these
Pallas kernels cover the reductions it fuses less aggressively (norm +
scale in one VMEM pass; log-softmax + gather in one pass over the vocab
axis). Odd shapes take the ``jnp`` form; the CPU backend interprets.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import backend


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * lax.rsqrt(var + eps) * w_ref[...].astype(
        jnp.float32)).astype(o_ref.dtype)


def rms_norm_fused(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                   block_rows: int = 256,
                   interpret: Optional[bool] = None) -> jax.Array:
    """RMSNorm over the last axis in one VMEM pass. x: [..., D], w: [D]."""
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if interpret is None:
        interpret = backend.on_cpu()
    if rows == 0 or D % 8 or rows % min(block_rows, rows):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(
            x.dtype)
    block_rows = min(block_rows, rows)

    from jax.experimental import pallas as pl

    xr = x.reshape(rows, D)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret,
    )(xr, w)
    return out.reshape(x.shape)


def softmax_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean NLL over all positions. logits [..., V], targets [...] int.

    Written so XLA fuses the log-softmax reduction with the label gather in
    one pass over the vocab axis (no [*, V] log-prob materialization beyond
    the fused loop); kept in pure jax because the fusion is already optimal
    under XLA on TPU.
    """
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    picked = jnp.take_along_axis(
        shifted, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
