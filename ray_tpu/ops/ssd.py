"""Mamba-2's selective scan in its chunked form (SSD: "Transformers are
SSMs", arXiv:2405.21060, section 6): the repo's second recurrent operator.

Per head a state ``S [P, N]`` in float32, from zero, one scalar decay a
head and position:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``x_t [P]`` a head's inputs, ``B_t``, ``C_t [N]`` shared by the heads of a
group, ``dt_t > 0``, ``A < 0`` and ``D`` one number a head. ``ssd_chunk``
computes it in chunks of ``chunk`` positions. With ``a_t = dt_t A`` and
``cum`` its running sum inside a chunk (this position's included), and
``S`` the state the chunk starts from:

    Y  = (C B^T * L) (dt * X) + exp(cum) * (C S^T),
         L_ij = exp(cum_i - cum_j) for j <= i, else 0
    S' = exp(cum_last) S + ((dt * X) * exp(cum_last - cum))^T B

The in-chunk part (the ``[chunk, chunk]`` scores of a group, the decay's
segment sums ``L`` of a head, the chunk's own contribution to the state) is
computed for all chunks at once; a ``lax.scan`` over the chunks carries
``S``. The decay is a scalar a head, so every exponent here is a difference
``cum_i - cum_j <= 0`` taken before its exponential: nothing overflows,
whatever ``dt A`` (Kimi Delta Attention's per-channel decay needs the split
``ops/kda.py`` is built round; this does not).

Products take their operands in the type of ``x`` (bfloat16 in the model,
float32 in the tests) and accumulate in float32; ``dt``, ``A``, the running
sums, ``L`` and the carried state are float32. The backward pass is JAX's
transpose of this chunked form under ``jax.checkpoint``: a layer keeps the
scan's inputs and none of a chunk's scores. A sequence that is no whole
number of chunks is padded with positions of ``dt = 0``, which leave the
state as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def ssd_chunk(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
              c: jax.Array, d: jax.Array, chunk: int = 128) -> jax.Array:
    """x [B, S, H, P], dt [B, S, H] (float32, after its softplus), a [H]
    (float32, negative), b and c [B, S, G, N] (H a multiple of G: head h
    reads group ``h // (H // G)``), d [H] -> y [B, S, H, P] in x's type."""
    return jax.checkpoint(functools.partial(_ssd_chunk, chunk))(
        x, dt, a, b, c, d)


def _ssd_chunk(chunk, x, dt, a, b, c, d):
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    R, Q = H // G, chunk
    f32 = jnp.float32
    pad = -S % Q
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    n = (S + pad) // Q
    dt = dt.astype(f32).reshape(B, n, Q, G, R)
    xdt = x.reshape(B, n, Q, G, R, P) * dt[..., None].astype(x.dtype)
    bs, cs = b.reshape(B, n, Q, G, N), c.reshape(B, n, Q, G, N)
    # The log-decay's running sum a head, chunk-major: [B, n, G, R, Q].
    cum = jnp.cumsum(
        (dt * a.astype(f32).reshape(G, R)).transpose(0, 1, 3, 4, 2), axis=-1)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    seg = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                            -jnp.inf))                        # L [.., Q, Q]
    scores = jnp.einsum("bnigs,bnjgs->bngij", cs, bs,
                        preferred_element_type=f32)           # C B^T a group
    y = jnp.einsum("bngrij,bnjgrp->bnigrp",
                   (scores[:, :, :, None] * seg).astype(x.dtype), xdt,
                   preferred_element_type=f32)
    # What each chunk adds to the state by its end, and how far it decays
    # the state it started from.
    to_end = jnp.exp(cum[..., -1:] - cum)                     # [B,n,G,R,Q]
    added = jnp.einsum(
        "bnjgrp,bnjgs->bngrps",
        xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None].astype(x.dtype),
        bs, preferred_element_type=f32)
    through = jnp.exp(cum[..., -1])                           # [B, n, G, R]

    def carry(state, chunk_of):
        added, through = chunk_of
        return through[..., None, None] * state + added, state

    _, before = lax.scan(
        carry, jnp.zeros((B, G, R, P, N), f32),
        (added.swapaxes(0, 1), through.swapaxes(0, 1)))
    y = y + jnp.einsum(
        "bnigs,bngrps->bnigrp", cs, before.swapaxes(0, 1).astype(x.dtype),
        preferred_element_type=f32) \
        * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(B, n * Q, H, P)[:, :S]
    x = x[:, :S]
    return (y + d.astype(f32)[:, None] * x.astype(f32)).astype(x.dtype)
