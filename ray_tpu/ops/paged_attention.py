"""Attention over a paged KV cache (vLLM's PagedAttention role,
reference: Kwon et al. — block-table indirection instead of one
contiguous KV region per sequence).

The cache is a pool of fixed-size blocks in preallocated arrays
``[num_blocks, block_size, n_kv_heads, head_dim]``; each sequence owns a
block table (list of block ids) mapping logical token positions to
physical slots, so sequences grow/shrink without moving bytes and freed
blocks are reusable by any sequence.

GQA stays GROUPED end-to-end: queries reshape to
``[B, n_kv_heads, group, head_dim]`` and contract against the cache at
``n_kv_heads`` width — the repeat-expanded ``n_heads``-wide K/V that the
training fallback used to materialize never exists on the decode path
(at large batch x long context that expansion would dominate HBM
traffic).

Two entry points:

- ``paged_attention_decode``: one query token per sequence (the
  continuous-batching decode step).
- ``paged_attention_prefill``: a CHUNK of query tokens per sequence
  attending over everything already written — cached prefix blocks
  (prefix-cache hits), earlier chunks, and the chunk itself (causal) —
  which is what chunked prefill and prefix-cache-skip both need.

Under tensor parallelism pass ``mesh``/``rules``: the gathered context
and the grouped scores are constrained to the ``kv_heads`` mesh axis,
so each chip attends only its local head shard of its local cache shard
(the Megatron pattern; the output projection's psum lives in the model).

This is the jax-level formulation (gather + masked grouped einsum): XLA
tiles the einsums onto the MXU directly, and it is exact on every
backend, which is what the engine's token-parity tests pin. A Pallas
kernel that walks the block table with scalar prefetch (never
materializing the gathered [B, S, n_kv_heads, head_dim] context in HBM)
drops in behind the same signature; the dispatch seam below mirrors
ops/flash_attention.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _constrain(x, mesh, rules, *logical):
    if mesh is None:
        return x
    from ray_tpu.parallel.sharding import constrain_logical

    return constrain_logical(x, mesh, rules, *logical)


@jax.named_scope("seg.attn_core")
def paged_attention_decode(q, k_cache, v_cache, block_tables,
                           context_lens, mesh=None, rules=None):
    """Single-token attention of each sequence against its paged context.

    q [B, n_heads, head_dim]; k/v cache [num_blocks, block_size,
    n_kv_heads, head_dim]; block_tables [B, max_blocks] int32 (rows
    padded with the null block); context_lens [B] int32.

    Returns ``[B, n_heads, head_dim]`` in ``q.dtype``. Cache slots at or
    past ``context_lens[b]`` (including every slot of padded block-table
    entries) are masked out of the softmax, so trash writes into the
    null block or not-yet-filled slots never contribute.
    """
    B, Hq, Dh = q.shape
    _, block_size, Hkv, _ = k_cache.shape
    if Hq % Hkv:
        raise ValueError(f"n_heads {Hq} % n_kv_heads {Hkv} != 0")
    group = Hq // Hkv
    # Gather this batch's context: [B, max_blocks*block_size, Hkv, Dh].
    k = k_cache[block_tables].reshape(B, -1, Hkv, Dh)
    v = v_cache[block_tables].reshape(B, -1, Hkv, Dh)
    k = _constrain(k, mesh, rules, None, None, "kv_heads", "head_dim")
    v = _constrain(v, mesh, rules, None, None, "kv_heads", "head_dim")
    s_len = k.shape[1]

    qg = q.reshape(B, Hkv, group, Dh)
    qg = _constrain(qg, mesh, rules, None, "kv_heads", None, "head_dim")
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k) * (Dh ** -0.5)
    valid = jnp.arange(s_len)[None, :] < context_lens[:, None]  # [B, S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v)
    o = _constrain(o, mesh, rules, None, "kv_heads", None, "head_dim")
    return o.reshape(B, Hq, Dh)


@jax.named_scope("seg.attn_core")
def paged_attention_prefill(q, k_cache, v_cache, block_tables,
                            q_positions, mesh=None, rules=None):
    """Chunked-prefill attention: C query tokens per sequence against
    the paged context written so far (cached prefix + this chunk).

    q [B, C, n_heads, head_dim]; q_positions [B, C] int32 — the absolute
    position of each chunk token (the chunk's K/V must already be
    scattered into the cache; a token attends every cache slot at
    position <= its own, which covers the cached prefix, earlier chunks,
    and in-chunk causality in one mask). Padded chunk tails and padded
    batch rows produce garbage rows the caller ignores — their writes
    land at positions no real query ever admits.

    Returns ``[B, C, n_heads, head_dim]`` in ``q.dtype``.
    """
    B, C, Hq, Dh = q.shape
    _, block_size, Hkv, _ = k_cache.shape
    if Hq % Hkv:
        raise ValueError(f"n_heads {Hq} % n_kv_heads {Hkv} != 0")
    group = Hq // Hkv
    k = k_cache[block_tables].reshape(B, -1, Hkv, Dh)
    v = v_cache[block_tables].reshape(B, -1, Hkv, Dh)
    k = _constrain(k, mesh, rules, None, None, "kv_heads", "head_dim")
    v = _constrain(v, mesh, rules, None, None, "kv_heads", "head_dim")
    s_len = k.shape[1]

    qg = q.reshape(B, C, Hkv, group, Dh)
    s = jnp.einsum("bchgd,bshd->bhgcs", qg, k) * (Dh ** -0.5)
    valid = (jnp.arange(s_len)[None, None, :]
             <= q_positions[:, :, None])                 # [B, C, S]
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgcs,bshd->bchgd", p, v)
    return o.reshape(B, C, Hq, Dh)
