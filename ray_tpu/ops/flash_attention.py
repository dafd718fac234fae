"""Flash attention as a Pallas TPU kernel.

Blockwise attention with online softmax: the [S, S] score matrix never
materializes in HBM — each (q-block, k-block) tile of scores lives in VMEM,
feeding the MXU with [block, head_dim] @ [head_dim, block] matmuls while
running max/sum accumulators carry the normalization (same recurrence the
ring_attention layer uses across chips; this kernel is the within-chip
block loop).

Grid: (batch*heads, num_q_blocks); the k-loop runs inside the kernel via
fori_loop over VMEM blocks. Shapes outside ``kernel_accepts`` take the
dense ``jnp`` form; the backend never decides that (``ops/backend.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import backend

NEG_INF = -1e30

# What the TPU compiler accepts, found by compiling ahead of time for a
# v5e topology (tests/test_tpu_aot.py holds both sides of each limit):
#  - the log-sum-exp store ``lse_ref[:, dslice(qi * block_q, block_q)]``
#    needs a lane-aligned offset, so blocks are multiples of 128;
#  - every program keeps whole per-head arrays resident in VMEM (K and V;
#    q, dout and out in the dk/dv kernel) under a 16 MiB scoped limit.
#    A per-head array (S x D x itemsize) of 3 MiB compiles in the forward
#    and both backward kernels; 3.5 MiB is refused.
_LANE = 128
_VMEM_HEAD_ARRAY_BYTES = 3 << 20


def _fallback(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                 seq_k: int, causal: bool, scale: float, block_q: int):
    from jax.experimental import pallas as pl

    q = q_ref[...] * scale                      # [block_q, d]
    qi = pl.program_id(1)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(kb * block_k, block_k), :]     # [block_k, d]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jnp.arange(block_q)
            k_pos = kb * block_k + jnp.arange(block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    if causal:
        # Only k-blocks at or before this q-block contribute.
        last = (qi + 1) * block_q
        num_needed = (last + block_k - 1) // block_k
        num_kb_run = jnp.minimum(num_kb, num_needed)
    else:
        num_kb_run = num_kb
    m, l, acc = lax.fori_loop(0, num_kb_run, body, (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)
    # Log-sum-exp of the (scaled) scores: the backward kernels rebuild
    # each probability tile as exp(s - lse) without a second online pass.
    # Stored sublane-broadcast as [8, Sq] per head — TPU block specs
    # reject 1-D vectors, and 8 sublanes is the cheapest legal layout
    # (8x the payload vs the 128x a lane-broadcast would cost).
    lse_ref[:, pl.dslice(qi * block_q, block_q)] = lax.broadcast_in_dim(
        m + jnp.log(l), (8, block_q), (1,))


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                        dq_ref, *, block_k: int, seq_k: int, causal: bool,
                        scale: float, block_q: int):
    from jax.experimental import pallas as pl

    q = q_ref[...]                               # [block_q, d]
    do = do_ref[...]
    qi = pl.program_id(1)
    lse = lse_ref[...][0]                        # [block_q] f32
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[...].astype(jnp.float32),
                    axis=-1)                     # [block_q] f32
    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, dq):
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jnp.arange(block_q)
            k_pos = kb * block_k + jnp.arange(block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])            # masked lanes -> 0
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jnp.dot(ds.astype(q.dtype), k,
                            preferred_element_type=jnp.float32)

    if causal:
        last = (qi + 1) * block_q
        num_needed = (last + block_k - 1) // block_k
        num_kb_run = jnp.minimum(num_kb, num_needed)
    else:
        num_kb_run = num_kb
    dq = lax.fori_loop(0, num_kb_run, body, dq)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
                         dk_ref, dv_ref, *, block_q: int, seq_q: int,
                         causal: bool, scale: float, block_k: int):
    from jax.experimental import pallas as pl

    k = k_ref[...]                               # [block_k, d]
    v = v_ref[...]
    ki = pl.program_id(1)
    d = k.shape[-1]
    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)

    num_qb = seq_q // block_q

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[pl.dslice(qb * block_q, block_q), :]
        do = do_ref[pl.dslice(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.dslice(qb * block_q, block_q)]
        delta = jnp.sum(
            do.astype(jnp.float32)
            * o_ref[pl.dslice(qb * block_q, block_q), :].astype(jnp.float32),
            axis=-1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jnp.arange(block_q)
            k_pos = ki * block_k + jnp.arange(block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])            # [block_q, block_k]
        pT = p.astype(do.dtype).T
        dv = dv + jnp.dot(pT, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # q-blocks strictly before this k-block are fully masked.
        qb_start = (ki * block_k) // block_q
    else:
        qb_start = 0
    dk, dv = lax.fori_loop(qb_start, num_qb, body, (dk, dv))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _fallback_grouped(q, k, v, causal, scale):
    """Grouped-GQA dense reference: q [B, Hq, S, D] folds to
    [B, Hkv, group, S, D] and contracts against K/V at n_kv_heads width
    — no n_heads-wide K/V is ever materialized."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * scale
    if causal:
        sk = k.shape[2]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v)
    return o.reshape(B, Hq, Sq, D)


def _auto_block(seq: int, cap: int = 512) -> int:
    """Largest power-of-2 divisor of `seq`, capped. Measured on TPU v5e
    (seq 1024-4096, head dim 64/128): 512x512 tiles run the forward
    2.3x and fwd+bwd 1.2-1.3x faster than 128x128 — bigger tiles keep
    the MXU busy longer per VMEM round trip."""
    b = 1
    while b < cap and seq % (b * 2) == 0:
        b *= 2
    return b


def kernel_accepts(sq: int, sk: int, d: int, itemsize: int,
                   block_q: int, block_k: int, *, interpret: bool) -> bool:
    """The one statement of which shapes run the kernels. The tiling
    rules hold in both modes; the lane and VMEM limits are the TPU
    compiler's, so the interpreter (CPU tests, small tiles) skips them."""
    if sq < 8 or sk < 8 or d % 8 or sq % block_q or sk % block_k:
        return False
    if interpret:
        return True
    if block_q % _LANE or block_k % _LANE:
        return False
    return max(sq, sk) * d * itemsize <= _VMEM_HEAD_ARRAY_BYTES


def use_flash(sq: int, sk: int, d: int, dtype) -> bool:
    """Model-path dispatch: the compiled kernel wherever it is accepted,
    the model's dense ``jnp`` attention on the CPU backend and for the
    shapes the guard refuses."""
    return not backend.on_cpu() and kernel_accepts(
        sq, sk, d, jnp.dtype(dtype).itemsize, _auto_block(sq),
        _auto_block(sk), interpret=False)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q/k/v: [B, H, S, D] -> [B, H, S, D]. GQA: repeat kv heads first.

    Block sizes default to an autotuned schedule (see _auto_block); pass
    explicit block_q/block_k to override."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if interpret is None:
        interpret = backend.on_cpu()
    block_q = min(block_q or _auto_block(Sq), Sq)
    block_k = min(block_k or _auto_block(Sk), Sk)
    if not kernel_accepts(Sq, Sk, D, q.dtype.itemsize, block_q, block_k,
                          interpret=bool(interpret)):
        return _fallback(q, k, v, causal, scale)
    return _flash_core(q, k, v, causal, scale, block_q, block_k,
                       bool(interpret))


def flash_attention_grouped(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA flash attention with K/V kept at ``n_kv_heads`` width:
    q [B, Hq, S, D], k/v [B, Hkv, S, D] (Hkv divides Hq) -> [B, Hq, S, D].

    The grid runs one program per QUERY head; each program's K/V block
    specs index-map to the head's kv group — the repeat-expanded
    n_heads-wide K/V that ``flash_attention`` requires never exists in
    HBM (at inference batch x context that expansion is pure wasted
    bandwidth). FORWARD-ONLY: the FA2 backward kernels want matched
    head counts, so the differentiable training path keeps the expanded
    form; inference (prefill-with-cache) dispatches here.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"n_heads {Hq} % n_kv_heads {Hkv} != 0")
    if scale is None:
        scale = D ** -0.5
    if Hq == Hkv:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    if interpret is None:
        interpret = backend.on_cpu()
    block_q = min(block_q or _auto_block(Sq), Sq)
    block_k = min(block_k or _auto_block(Sk), Sk)
    if not kernel_accepts(Sq, Sk, D, q.dtype.itemsize, block_q, block_k,
                          interpret=bool(interpret)):
        return _fallback_grouped(q, k, v, causal, scale)
    return _flash_forward_grouped(q, k, v, causal, scale, block_q,
                                  block_k, bool(interpret))


def _flash_forward_grouped(q, k, v, causal, scale, block_q, block_k,
                           interpret):
    """Same online-softmax kernel as ``_flash_forward``; only the K/V
    BlockSpec index maps differ — program ``b`` over the flattened
    [B*Hq] axis reads kv row ``(b // Hq) * Hkv + (b % Hq) // group``."""
    from jax.experimental import pallas as pl

    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        scale=scale, block_q=block_q)

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Sk, D)
    vr = v.reshape(B * Hkv, Sk, D)

    def kv_index(b, i):
        return ((b // Hq) * Hkv + (b % Hq) // group, 0, 0)

    call = pl.pallas_call(
        kernel,
        name="flash_fwd_grouped",
        grid=(B * Hq, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), kv_index),
            pl.BlockSpec((None, Sk, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, 8, Sq), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("flash_fwd_grouped"):
        out, _lse = call(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    """Returns (out [B,H,Sq,D], lse [B,H,Sq])."""
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        scale=scale, block_q=block_q)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)

    call = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, Sq), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("flash_fwd"):
        out, lse = call(qr, kr, vr)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, 8, Sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret)[0]


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret,
                    res, dout):
    """Flash-attention backward as two Pallas kernels (the FA2 split):
    a dq kernel gridded over q-blocks and a dk/dv kernel gridded over
    k-blocks, each rebuilding its probability tile in VMEM from the
    forward's saved log-sum-exp. The [S, S] score matrix never touches
    HBM — the old pure-jax fallback spilled every [Sq, block_k] tile,
    which made the backward HBM-bound (~2 TFLOPS measured at seq 4096 on
    TPU v5e vs ~15 TFLOPS for this version)."""
    from jax.experimental import pallas as pl

    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    BH = B * H

    qr = q.reshape(BH, Sq, D)
    kr = k.reshape(BH, Sk, D)
    vr = v.reshape(BH, Sk, D)
    outr = out.reshape(BH, Sq, D)
    dor = dout.reshape(BH, Sq, D).astype(q.dtype)
    lser = lse.reshape(BH, 8, Sq)

    dq_kernel = functools.partial(
        _attn_bwd_dq_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        scale=scale, block_q=block_q)
    dq_call = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(qr, kr, vr, dor, outr, lser)

    dkv_kernel = functools.partial(
        _attn_bwd_dkv_kernel, block_q=block_q, seq_q=Sq, causal=causal,
        scale=scale, block_k=block_k)
    dkv_call = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 8, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(kr, vr, qr, dor, outr, lser)

    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


_flash_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)
