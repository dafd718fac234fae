"""Flash attention as a Pallas TPU kernel.

Blockwise attention with online softmax: the [S, S] score matrix never
materializes in HBM — each (q-block, k-block) tile of scores lives in VMEM,
feeding the MXU with [block, head_dim] @ [head_dim, block] matmuls while
running max/sum accumulators carry the normalization (same recurrence the
ring_attention layer uses across chips; this kernel is the within-chip
block loop).

Three kernels, each a grid of (batch*heads, blocks) with the loop over
the other axis inside (``fori_loop`` over slices of whole-head arrays in
VMEM). What a program holds beside its own blocks:

- ``flash_fwd`` / ``flash_fwd_grouped`` (grid over q-blocks): the head's
  K and V; in scratch the f32 output accumulator ``[block_q, D]`` and the
  running max and sum, ``[block_q, 128]`` each. Writes ``out`` and the
  log-sum-exp, lane-major ``[8, Sq]``.
- ``flash_bwd_dq`` (grid over q-blocks): the head's K and V; its blocks
  of q, do, o and lse; in scratch the f32 dq accumulator.
- ``flash_bwd_dkv`` (grid over k-blocks): the head's q, do, o and lse;
  in scratch the f32 dk and dv accumulators. Its score tiles are
  k-major, ``[block_k, block_q]``, so no tile is transposed.

The accumulators are scratch, updated in place, and not values carried
round the loop: the compiler copied every carried value at the head and
the tail of each iteration with the MXU idle (its schedule for a v5e,
read in PERF.md, PR 29).

The values may be narrower or wider than the queries and keys (latent
attention: 192 against 128): ``v``, ``o``, ``do``, ``dv`` and their
accumulators take the value width, the score products the query-key width,
and nothing is padded. At equal widths the kernels are what they were.

A causal grid skips the tiles that hold no unmasked position
(``tile_counts``). Shapes outside ``kernel_accepts`` take the dense
``jnp`` form; the backend never decides that (``ops/backend.py``).
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
#  - every program keeps whole per-head arrays resident in VMEM (K and V
#    in the forward and dq kernels; q, dout and out in the dk/dv kernel)
#    under a 16 MiB scoped limit. A per-head array (S x D x itemsize) of
#    3 MiB compiles in the forward and both backward kernels; 3.5 MiB is
#    refused.
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


def _row_tiles(qi, block_q: int, block_k: int, num_kb: int, causal: bool):
    """The k-tiles of q-block ``qi`` as ``(interior, run)``: tiles
    ``[0, interior)`` lie wholly inside the causal region, ``[interior,
    run)`` are crossed by the diagonal (a tile needs the mask iff its
    last key position exceeds its first query position), the rest hold
    no unmasked position. The kernels loop over ``[0, run)`` and mask
    every tile: in the v5e schedule the mask's compare and select ride in
    free ALU slots, and a second, unmasked loop bought nothing (PERF.md,
    PR 29). ``qi`` is a ``program_id``, an int or an array of ints."""
    if not causal:
        return num_kb, num_kb
    interior = jnp.minimum(num_kb, (qi * block_q + 1) // block_k)
    run = jnp.minimum(num_kb, ((qi + 1) * block_q + block_k - 1) // block_k)
    return interior, run


def _col_tiles(ki, block_q: int, block_k: int, num_qb: int, causal: bool):
    """The q-tiles of k-block ``ki`` as ``(start, interior)``, the same
    rule read down a column: ``[0, start)`` hold no unmasked position,
    ``[start, interior)`` are crossed by the diagonal, ``[interior,
    num_qb)`` need no mask. The dk/dv kernel loops over ``[start,
    num_qb)``."""
    if not causal:
        return 0, 0
    start = jnp.minimum(num_qb, (ki * block_k) // block_q)
    interior = jnp.minimum(
        num_qb, ((ki + 1) * block_k - 1 + block_q - 1) // block_q)
    return start, interior


def tile_counts(sq: int, sk: int, block_q: int, block_k: int,
                causal: bool) -> tuple:
    """``(interior, diagonal, skipped)`` tiles of one head's grid, from
    the bounds the kernels loop by: run needing no mask, run needing it,
    not run. Shapes alone decide it: 28 / 8 / 28 at S 4096 with
    512 x 512 tiles, so 36 of 64 tiles are run for 32.06 tiles' worth of
    causal pairs."""
    num_qb, num_kb = sq // block_q, sk // block_k
    if not causal:
        return num_qb * num_kb, 0, 0
    interior, run = _row_tiles(jnp.arange(num_qb), block_q, block_k,
                               num_kb, causal)
    interior, run = int(jnp.sum(interior)), int(jnp.sum(run))
    return interior, run - interior, num_qb * num_kb - run


def _dot_nt(a, b):
    """``a @ b.T`` as one contraction over the last axes: the MXU's NT
    form, no transpose of an operand."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _causal_mask(shape, q_axis: int, q_start, k_start):
    """True where the query position (along ``q_axis`` of a score tile
    of ``shape``) is at or after the key position. Two 2-D iotas: the
    compiler relayouts a 1-D ``arange`` broadcast to the tile on every
    tile (a fifth of the forward's schedule for a v5e, PERF.md, PR 29)."""
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos >= k_pos


def _lanes(x, n: int):
    """``x`` ``[rows, w]``, one value a row repeated along its lanes, at
    ``n`` lanes: a slice or a concatenation of whole copies, neither of
    which moves data between lanes."""
    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    if n % w == 0:
        return jnp.concatenate([x] * (n // w), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                 *, block_k: int, seq_k: int, causal: bool, scale: float,
                 block_q: int):
    """One q-block against the head's K and V. The running max ``m``, the
    running sum ``l`` and the output accumulator live in VMEM scratch and
    are updated in place; ``m`` and ``l`` are ``[block_q, w]`` with a row's
    value in every lane (``l``: a row's partial sums, one a lane, added up
    after the loop), so no tile reduces or relayouts them."""
    from jax.experimental import pallas as pl

    q = q_ref[...] * scale                      # [block_q, d]
    qi = pl.program_id(1)
    w = m_ref.shape[1]
    d = acc_ref.shape[1]                        # the value width
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(kb, carry):
        k = k_ref[pl.dslice(kb * block_k, block_k), :]     # [block_k, d]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = _dot_nt(q, k)
        if causal:
            s = jnp.where(_causal_mask(s.shape, 0, qi * block_q,
                                       kb * block_k), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        # One lane-width of key columns at a time: each probability
        # sub-tile goes from exp to the MXU without a round trip.
        p_sum = pv = 0.0
        for j in range(block_k // w):
            p = jnp.exp(s[:, j * w:(j + 1) * w] - m_new)
            p_sum = p_sum + p
            pv = pv + jnp.dot(p.astype(v.dtype), v[j * w:(j + 1) * w, :],
                              preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p_sum
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + pv
        return carry

    _, run = _row_tiles(qi, block_q, block_k, seq_k // block_k, causal)
    lax.fori_loop(0, run, body, 0)
    l = jnp.maximum(jnp.sum(l_ref[...], axis=-1, keepdims=True), 1e-30)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
    # Log-sum-exp of the (scaled) scores: the backward kernels rebuild
    # each probability tile as exp(s - lse) without a second online pass.
    # Stored sublane-broadcast as [8, Sq] per head — TPU block specs
    # reject 1-D vectors, and 8 sublanes is the cheapest legal layout
    # (8x the payload vs the 128x a lane-broadcast would cost).
    lse_ref[:, pl.dslice(qi * block_q, block_q)] = lax.broadcast_in_dim(
        (m_ref[...][:, :1] + jnp.log(l))[:, 0], (8, block_q), (1,))


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                        dq_ref, acc_ref, *, block_k: int, seq_k: int,
                        causal: bool, scale: float, block_q: int):
    from jax.experimental import pallas as pl

    q = q_ref[...]                               # [block_q, d]
    do = do_ref[...]
    qi = pl.program_id(1)
    lse = lse_ref[...][0]                        # [block_q] f32
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[...].astype(jnp.float32),
                    axis=-1)                     # [block_q] f32
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(kb, carry):
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        dp = _dot_nt(do, v)
        s = _dot_nt(q, k) * scale
        if causal:
            s = jnp.where(_causal_mask(s.shape, 0, qi * block_q,
                                       kb * block_k), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])            # masked lanes -> 0
        ds = p * (dp - delta[:, None])
        acc_ref[...] += jnp.dot(ds.astype(q.dtype), k,
                                preferred_element_type=jnp.float32)
        return carry

    _, run = _row_tiles(qi, block_q, block_k, seq_k // block_k, causal)
    lax.fori_loop(0, run, body, 0)
    dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                         seq_q: int, causal: bool, scale: float,
                         block_k: int):
    """Score tiles k-major, ``[block_k, block_q]``: every matmul is NN or
    NT, so no score-sized tile is transposed, and ``lse`` (stored
    lane-major) and ``delta`` are rows that broadcast down the sublanes."""
    from jax.experimental import pallas as pl

    k = k_ref[...]                               # [block_k, d]
    v = v_ref[...]
    ki = pl.program_id(1)
    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def body(qb, carry):
        rows = pl.dslice(qb * block_q, block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        lse = lse_ref[0:1, rows]                 # [1, block_q]
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[rows, :].astype(jnp.float32),
            axis=-1)[None, :]
        sT = _dot_nt(k, q) * scale               # [block_k, block_q]
        if causal:
            sT = jnp.where(_causal_mask(sT.shape, 1, qb * block_q,
                                        ki * block_k), sT, NEG_INF)
        pT = jnp.exp(sT - lse)
        dv_acc[...] += jnp.dot(pT.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dsT = pT * (_dot_nt(v, do) - delta)
        dk_acc[...] += jnp.dot(dsT.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
        return carry

    num_qb = seq_q // block_q
    start, _ = _col_tiles(ki, block_q, block_k, num_qb, causal)
    lax.fori_loop(start, num_qb, body, 0)
    dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _acc_scratch(block: int, d: int):
    """An f32 accumulator in VMEM, updated in place by the tile loop."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((block, d), jnp.float32)


def _fwd_scratch(block_q: int, block_k: int, d: int) -> list:
    """The forward's accumulator (``d``: the value width), running max
    and running sum; the two
    statistics as wide as a vreg has lanes (narrower only for the
    interpreter's small blocks)."""
    w = math.gcd(block_k, _LANE)
    return [_acc_scratch(block_q, d), _acc_scratch(block_q, w),
            _acc_scratch(block_q, w)]


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
    """Largest power-of-2 divisor of `seq`, capped at 512: the largest
    tile whose f32 scores (1 MiB) the kernels hold with their whole-head
    arrays. Not measured against smaller tiles on the present harness."""
    b = 1
    while b < cap and seq % (b * 2) == 0:
        b *= 2
    return b


def kernel_accepts(sq: int, sk: int, d: int, itemsize: int,
                   block_q: int, block_k: int, *, interpret: bool,
                   dv: Optional[int] = None) -> bool:
    """The one statement of which shapes run the kernels (``d``: the
    query-key width, ``dv``: the values', where it differs; the wider of
    the two is what a whole-head array in VMEM is bounded by). The tiling
    rules hold in both modes; the lane and VMEM limits are the TPU
    compiler's, so the interpreter (CPU tests, small tiles) skips them."""
    dv = d if dv is None else dv
    if (sq < 8 or sk < 8 or d % 8 or dv % 8 or sq % block_q
            or sk % block_k):
        return False
    if interpret:
        return True
    if block_q % _LANE or block_k % _LANE:
        return False
    return max(sq, sk) * max(d, dv) * itemsize <= _VMEM_HEAD_ARRAY_BYTES


def use_flash(sq: int, sk: int, d: int, dtype,
              dv: Optional[int] = None) -> bool:
    """Model-path dispatch: the compiled kernel wherever it is accepted,
    the model's dense ``jnp`` attention on the CPU backend and for the
    shapes the guard refuses."""
    return not backend.on_cpu() and kernel_accepts(
        sq, sk, d, jnp.dtype(dtype).itemsize, _auto_block(sq),
        _auto_block(sk), interpret=False, dv=dv)


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
    """q/k: [B, H, S, D], v: [B, H, S, Dv] -> [B, H, S, Dv],
    differentiable. K and V come at the query heads' count
    (``flash_attention_grouped`` takes fewer, forward only).

    Block sizes default to ``_auto_block``'s; pass explicit
    block_q/block_k to override."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if interpret is None:
        interpret = backend.on_cpu()
    block_q = min(block_q or _auto_block(Sq), Sq)
    block_k = min(block_k or _auto_block(Sk), Sk)
    if not kernel_accepts(Sq, Sk, D, q.dtype.itemsize, block_q, block_k,
                          interpret=bool(interpret), dv=v.shape[-1]):
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
        scratch_shapes=_fwd_scratch(block_q, block_k, D),
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
    """Returns (out [B,H,Sq,Dv], lse [B,H,8,Sq])."""
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        scale=scale, block_q=block_q)

    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, Dv)

    call = pl.pallas_call(
        kernel,
        name="flash_fwd",
        scratch_shapes=_fwd_scratch(block_q, block_k, Dv),
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, Dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 8, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, Sq), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("flash_fwd"):
        out, lse = call(qr, kr, vr)
    return out.reshape(B, H, Sq, Dv), lse.reshape(B, H, 8, Sq)


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
    forward's saved log-sum-exp, so the [S, S] score matrix never touches
    HBM. Each computes ``delta = rowsum(do * o)`` for itself: handing it
    over cost more than it saved (PERF.md, PR 29)."""
    from jax.experimental import pallas as pl

    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    BH = B * H

    qr = q.reshape(BH, Sq, D)
    kr = k.reshape(BH, Sk, D)
    vr = v.reshape(BH, Sk, Dv)
    outr = out.reshape(BH, Sq, Dv)
    dor = dout.reshape(BH, Sq, Dv).astype(q.dtype)
    lser = lse.reshape(BH, 8, Sq)

    dq_kernel = functools.partial(
        _attn_bwd_dq_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        scale=scale, block_q=block_q)
    dq_call = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        scratch_shapes=[_acc_scratch(block_q, D)],
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, Dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, Dv), lambda b, i: (b, i, 0)),
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
        scratch_shapes=[_acc_scratch(block_k, D), _acc_scratch(block_k, Dv)],
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, Dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq, Dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 8, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, Dv), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, Dv), v.dtype),
        ],
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(kr, vr, qr, dor, outr, lser)

    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


_flash_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)
