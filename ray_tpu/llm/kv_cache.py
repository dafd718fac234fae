"""Paged KV cache with copy-on-write shared prefix blocks: fixed-size
blocks in preallocated device arrays plus host-side block-table,
refcount, and content-hash bookkeeping (reference role: vLLM's
BlockSpaceManager + automatic prefix caching over PagedAttention —
Kwon et al.).

The device side is two arrays ``[L, num_blocks, block_size, n_kv_heads,
head_dim]`` built once by ``models.init_kv_cache`` (the HBM pool; under
tensor parallelism the ``n_kv_heads`` axis is sharded across the mesh).
The host side is pure integer bookkeeping: a free list, per-sequence
block tables, and — new in this tier — a **prefix cache**:

- Every FULL block of a sequence's prompt is content-hashed by its
  *parent-chain digest*: ``digest_i = H(digest_{i-1}, tokens_i)``, so a
  digest match guarantees the entire token prefix up to and including
  that block is identical. Partial tail blocks are never shared.
- ``allocate_prefix`` matches a new prompt's leading full blocks
  against registered digests and SHARES the hits (refcount++), so the
  engine skips recomputing those prefill tokens entirely
  (``prefill_tokens_saved``). At most ``len(prompt) - 1`` tokens are
  ever skipped — the last prompt position must be computed for logits —
  and a fully-cached prompt therefore writes into its final shared
  block, which **copies on write** first (``cow_copies``).
- Freeing a sequence decrements refcounts; only blocks that hit
  refcount 0 become reusable. Registered zero-ref blocks PARK in an LRU
  *cached-free* tier instead of the plain free list: they still serve
  prefix hits, and are reclaimed (digest entries removed — a later
  admit can never resurrect a reclaimed block) only when the free list
  runs dry.

Block 0 is the NULL block: it is never handed out, and every padded
block-table entry (and padded batch row) points at it, so the jitted
prefill/decode programs can scatter unconditionally — garbage writes
land in block 0 and the attention mask keeps them out of every softmax.

Accounting counters (``blocks_in_use``, peaks, totals, prefix hit/save
counters) are the observable contract the engine tests pin: a
mid-generation ``close()`` of a sequence sharing prefix blocks must
free only its private blocks.
"""

from __future__ import annotations

import functools
import hashlib
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["KVCacheOOM", "PagedKVCache", "chain_digests"]

NULL_BLOCK = 0

# Truncated hex digest length. 16 hex chars = 64 bits per chained link —
# collisions are negligible at any realistic cache size, and compact
# digests keep the router's replica prefix reports small on the wire.
_DIGEST_LEN = 16


def chain_digests(tokens: Sequence[int], block_size: int) -> List[str]:
    """Parent-chained content digests of every FULL block of ``tokens``.

    ``out[i]`` commits to ``tokens[: (i+1)*block_size]`` — the whole
    prefix, not just block ``i`` — so matching ``out[i]`` against a
    registered block implies every earlier block matched too. Shared by
    the cache (registration/matching) and the Serve prefix router
    (scoring replicas by cached-prefix overlap).
    """
    out: List[str] = []
    parent = b""
    for i in range(len(tokens) // block_size):
        blk = tokens[i * block_size:(i + 1) * block_size]
        h = hashlib.blake2b(digest_size=16)
        h.update(parent)
        h.update(np.asarray(blk, np.int64).tobytes())
        parent = h.digest()
        out.append(h.hexdigest()[:_DIGEST_LEN])
    return out


class KVCacheOOM(RuntimeError):
    """No free blocks for a required allocation (after eviction)."""


class PagedKVCache:
    """Host-side block manager for one preallocated paged KV pool."""

    def __init__(self, model_cfg, num_blocks: int, block_size: int,
                 dtype=None, *, enable_prefix_caching: bool = True,
                 mesh=None, rules=None):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is NULL)")
        from ray_tpu.models import init_kv_cache

        self.model_cfg = model_cfg
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.enable_prefix_caching = bool(enable_prefix_caching)
        self.mesh = mesh
        init = functools.partial(init_kv_cache, model_cfg, num_blocks,
                                 block_size, dtype)
        if mesh is not None:
            # TP decode: the pool is BORN sharded along n_kv_heads across
            # the mesh (no device ever holds all of it); every block id
            # indexes the same logical block on every shard, so the host
            # bookkeeping below is unchanged.
            import jax

            from ray_tpu.parallel.sharding import (
                kv_cache_specs,
                param_sharding_tree,
            )

            init = jax.jit(init, out_shardings=param_sharding_tree(
                mesh, kv_cache_specs(rules)))
        self.data = init()
        # LIFO free list, block 0 reserved as NULL.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._tables: Dict[int, List[int]] = {}
        self._ref: Dict[int, int] = {}           # block -> refcount
        self._block_key: Dict[int, str] = {}     # block -> chain digest
        self._key_block: Dict[str, int] = {}     # chain digest -> block
        # refcount-0 registered blocks, LRU order (oldest first).
        self._cached_free: "OrderedDict[int, str]" = OrderedDict()
        # per-sequence prompt digests + how many blocks are registered.
        self._prompt_digests: Dict[int, List[str]] = {}
        self._registered_upto: Dict[int, int] = {}
        self._lock = threading.Lock()
        self._block_copy = None  # lazily-jitted COW block copy
        # Aux pools (e.g. the spec-decode DRAFT model's KV) ride the
        # SAME block tables/refcounts: one host-side manager, N device
        # pools. Every lifecycle event that moves bytes (COW copy,
        # export, graft) covers every pool, so a sequence's draft cache
        # can never diverge from its flagship cache's block layout.
        self._aux: Dict[str, Dict[str, object]] = {}
        # -- accounting (engine tests/bench read these) --
        self.peak_blocks_in_use = 0
        self.total_blocks_allocated = 0
        self.total_blocks_freed = 0
        # -- prefix-cache counters --
        self.prefix_cache_queries = 0      # allocate_prefix calls
        self.prefix_cache_hits = 0         # queries with >= 1 cached token
        self.prefix_cache_query_tokens = 0  # prompt tokens seen by queries
        self.prefill_tokens_saved = 0      # tokens skipped via cache hits
        self.cow_copies = 0                # shared blocks copied on write
        self.cached_blocks_evicted = 0     # cached-free blocks reclaimed
        # -- disagg p2p shipping counters --
        self.blocks_exported = 0           # blocks packed for p2p publish
        self.blocks_grafted = 0            # p2p blocks scattered back in

    # ------------------------------------------------------------- capacity
    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # NULL block excluded

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by live sequences (cached-free blocks are
        reusable on demand, so they count as free)."""
        return self.usable_blocks - self.free_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free) + len(self._cached_free)

    @property
    def cached_free_blocks(self) -> int:
        return len(self._cached_free)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.block_size)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_for_tokens(n_tokens) <= self.free_blocks

    # ----------------------------------------------------- internal helpers
    def _pop_block(self) -> Optional[int]:
        """One reusable block: plain free list first, else reclaim the
        LRU cached-free block (its digest entries are removed FIRST, so
        a racing admit can never match — and resurrect — a block whose
        bytes are about to be overwritten)."""
        if self._free:
            return self._free.pop()
        if self._cached_free:
            block, key = self._cached_free.popitem(last=False)
            self._deregister(block)
            self.cached_blocks_evicted += 1
            return block
        return None

    def _deregister(self, block: int) -> None:
        key = self._block_key.pop(block, None)
        if key is not None and self._key_block.get(key) == block:
            del self._key_block[key]

    def _release_block(self, block: int) -> int:
        """Drop one reference; returns 1 when the block became free."""
        n = self._ref.get(block, 1) - 1
        if n > 0:
            self._ref[block] = n
            return 0
        self._ref.pop(block, None)
        key = self._block_key.get(block)
        if key is not None and self.enable_prefix_caching:
            self._cached_free[block] = key
            self._cached_free.move_to_end(block)
        else:
            self._deregister(block)
            self._free.append(block)
        self.total_blocks_freed += 1
        return 1

    def _activate_cached(self, block: int) -> None:
        """A prefix hit on a cached-free block pulls it back live."""
        self._cached_free.pop(block, None)

    def _note_alloc(self, n: int) -> None:
        self.total_blocks_allocated += n
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)

    # ----------------------------------------------------------- allocation
    def allocate(self, seq_id: int, n_tokens: int) -> bool:
        """Give ``seq_id`` a fresh (non-prefix-matched) table covering
        ``n_tokens`` positions. Returns False (allocating nothing) when
        the pool can't cover it — the scheduler parks the request."""
        need = self.blocks_for_tokens(n_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already allocated")
            if need > self.free_blocks:
                return False
            blocks = [self._pop_block() for _ in range(need)]
            for b in blocks:
                self._ref[b] = 1
            self._tables[seq_id] = blocks
            self._note_alloc(need)
            return True

    def allocate_prefix(self, seq_id: int, prompt: Sequence[int],
                        extra_tokens: int = 1) -> Optional[int]:
        """Allocate ``seq_id``'s table for ``len(prompt) + extra_tokens``
        positions, SHARING every leading full block whose chain digest
        is already cached. Returns the number of prompt tokens whose KV
        is already present (the engine skips prefilling them), or None
        when the pool can't cover the unshared remainder.

        At most ``len(prompt) - 1`` tokens are reported cached (the last
        prompt position must be computed for its logits); when the match
        extends into the written range — a fully-cached prompt — the
        boundary shared block is copied on write here, so the prefill
        scatter never touches a block another sequence references.
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        need = self.blocks_for_tokens(len(prompt) + extra_tokens)
        if not self.enable_prefix_caching:
            ok = self.allocate(seq_id, len(prompt) + extra_tokens)
            return 0 if ok else None
        digests = chain_digests(prompt, self.block_size)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already allocated")
            self.prefix_cache_queries += 1
            self.prefix_cache_query_tokens += len(prompt)
            matched: List[int] = []
            for d in digests:
                b = self._key_block.get(d)
                if b is None:
                    break
                matched.append(b)
            cached_len = min(len(matched) * self.block_size,
                             len(prompt) - 1)
            # A fully-cached prompt writes into its final matched block:
            # if that block has a LIVE holder the write will copy-on-
            # write, costing one extra block — reserve it up front so a
            # request that fits never parks on a failed COW pop.
            cow_blocks = 0
            if matched and cached_len < len(matched) * self.block_size:
                boundary = matched[cached_len // self.block_size]
                if self._ref.get(boundary, 0) >= 1:
                    cow_blocks = 1
            if need - len(matched) + cow_blocks > self.free_blocks - sum(
                    1 for b in matched if b in self._cached_free):
                # The fresh remainder doesn't fit even after reclaiming
                # every NON-matched cached-free block. (Matched blocks
                # sitting in cached-free must not be double-counted as
                # reclaimable — activating them below removes them from
                # that tier.)
                return None
            # Take the shared prefix: refcount++ (activating any block
            # parked in cached-free), then fresh blocks for the rest.
            for b in matched:
                self._activate_cached(b)
                self._ref[b] = self._ref.get(b, 0) + 1
            def _rollback(fresh):
                for f in fresh:
                    self._ref.pop(f, None)
                    self._free.append(f)
                for m in matched:
                    if self._release_block(m):
                        self.total_blocks_freed -= 1  # not a real free

            fresh: List[int] = []
            for _ in range(need - len(matched)):
                b = self._pop_block()
                if b is None:  # raced: roll everything back
                    _rollback(fresh)
                    return None
                self._ref[b] = 1
                fresh.append(b)
            table = matched + fresh
            # Fully-cached boundary: the prefill will write positions
            # [cached_len, ...) and cached_len falls INSIDE the last
            # matched block -> copy-on-write it now.
            if matched and cached_len < len(matched) * self.block_size:
                idx = cached_len // self.block_size
                try:
                    table[idx] = self._make_private(table[idx])
                except KVCacheOOM:
                    _rollback(fresh)
                    return None
            self._tables[seq_id] = table
            self._prompt_digests[seq_id] = digests
            self._registered_upto[seq_id] = 0
            self._note_alloc(need - len(matched))
            if cached_len > 0:
                self.prefix_cache_hits += 1
                self.prefill_tokens_saved += cached_len
            return cached_len

    def _make_private(self, block: int) -> int:
        """Return a privately-owned, unregistered block with ``block``'s
        content: the block itself if this sequence is the only holder
        (deregistered — its content is about to change), else a fresh
        copy-on-write clone."""
        if self._ref.get(block, 1) <= 1:
            self._deregister(block)
            return block
        new = self._pop_block()
        if new is None:
            raise KVCacheOOM("no free block for copy-on-write")
        self._copy_block_data(block, new)
        self._ref[block] -= 1
        self._ref[new] = 1
        self._note_alloc(1)  # COW is a real allocation: keep the
        self.cow_copies += 1  # allocated/freed/peak contract balanced
        return new

    def _copy_block_data(self, src: int, dst: int) -> None:
        """Device-side block copy (K and V, all layers). Jitted with the
        pool donated so XLA updates the arrays IN PLACE on accelerators
        — an eager ``.at[].set`` would materialize a second full pool
        (2x HBM transient + full-pool copy) for a one-block COW. Block
        ids ride as traced scalars, so every COW hits one compiled
        program."""
        if self._block_copy is None:
            import jax

            from ray_tpu.ops import backend

            donate = () if backend.on_cpu() else (0,)
            self._block_copy = jax.jit(
                lambda arr, s, d: arr.at[:, d].set(arr[:, s]),
                donate_argnums=donate)
        import jax.numpy as jnp

        s = jnp.int32(src)
        d = jnp.int32(dst)
        # Aux pools (draft KV) share the block layout, so a COW must
        # copy EVERY pool — a draft cache left pointing at the donor
        # block would silently read another sequence's context.
        for pool in (self.data, *self._aux.values()):
            for name in ("k", "v"):
                pool[name] = self._block_copy(pool[name], s, d)

    def ensure_slot(self, seq_id: int, position: int) -> bool:
        """Grow ``seq_id``'s table so ``position`` has a physical slot
        this sequence may WRITE (at most one new block per decode step;
        a shared or registered block containing the slot goes private
        first). False on pool-empty — the scheduler's eviction policy
        decides who pays."""
        with self._lock:
            table = self._tables[seq_id]
            need_len = position // self.block_size + 1
            if need_len <= len(table):
                idx = position // self.block_size
                b = table[idx]
                if self._ref.get(b, 1) > 1 or b in self._block_key:
                    try:
                        table[idx] = self._make_private(b)
                    except KVCacheOOM:
                        return False
                return True
            b = self._pop_block()
            if b is None:
                return False
            self._ref[b] = 1
            table.append(b)
            self._note_alloc(1)
            return True

    def free(self, seq_id: int) -> int:
        """Release ``seq_id``'s references. Returns the number of blocks
        that actually became free (shared blocks stay with their other
        holders; registered ones park in the cached-free tier)."""
        with self._lock:
            blocks = self._tables.pop(seq_id, None)
            self._prompt_digests.pop(seq_id, None)
            self._registered_upto.pop(seq_id, None)
            if not blocks:
                return 0
            return sum(self._release_block(b) for b in reversed(blocks))

    # ------------------------------------------------- aux pools + shipping
    def attach_aux(self, name: str, model_cfg, dtype=None) -> None:
        """Attach a second device pool (same ``num_blocks`` ×
        ``block_size`` geometry, possibly a different model config —
        the spec-decode DRAFT cache) that rides this manager's block
        tables. Aux pools are copied on COW, packed by
        ``export_blocks`` and scattered by ``graft_blocks``."""
        if self.mesh is not None:
            raise ValueError("aux pools are not supported under tensor "
                             "parallelism")
        from ray_tpu.models import init_kv_cache

        with self._lock:
            if name in self._aux:
                raise ValueError(f"aux pool {name!r} already attached")
            self._aux[name] = init_kv_cache(
                model_cfg, self.num_blocks, self.block_size, dtype)

    def aux_data(self, name: str):
        return self._aux[name]

    def set_aux_data(self, name: str, data) -> None:
        self._aux[name] = data

    def export_blocks(self, seq_id: int, start_block: int = 0) -> dict:
        """Pack ``seq_id``'s block data from ``start_block`` on into a
        host-side payload (per-layer block ranges for every pool) —
        what a disagg prefill replica publishes as an owner-resolved
        p2p object. ``start_block`` implements tail-only shipping: a
        decode replica whose prefix cache already holds the leading
        blocks asks only for the unshared remainder.

        Device arrays are immutable values, so the gather runs outside
        the lock against a snapshot reference — a concurrent step's
        functional cache update cannot corrupt the export."""
        with self._lock:
            table = list(self._tables[seq_id])
            data = self.data
            aux = {n: dict(p) for n, p in self._aux.items()}
        blocks = table[start_block:]
        payload = {
            "start_block": int(start_block),
            "blocks": len(blocks),
            "block_size": self.block_size,
        }
        if blocks:
            import jax.numpy as jnp

            idx = jnp.asarray(np.asarray(blocks, np.int32))
            payload["k"] = np.asarray(data["k"][:, idx])
            payload["v"] = np.asarray(data["v"][:, idx])
            payload["aux"] = {
                n: {"k": np.asarray(p["k"][:, idx]),
                    "v": np.asarray(p["v"][:, idx])}
                for n, p in aux.items()
            }
        with self._lock:
            self.blocks_exported += len(blocks)
        return payload

    def graft_blocks(self, seq_id: int, payload: dict,
                     start_block: Optional[int] = None) -> int:
        """Scatter a peer's exported block payload into ``seq_id``'s
        table, starting at ``start_block`` (default: the payload's own
        start). A graft start past the payload's start skips leading
        payload blocks — the decode replica's prefix cache covered more
        than the shipping plan assumed, and shared blocks must NEVER be
        written. Every target block is asserted privately owned and
        unregistered. Returns blocks grafted.

        Callers serialize against the engine step loop (the engine
        grafts under its step lock): the scatter is a read-modify-write
        of the pool arrays and must not interleave with a step's own
        functional update."""
        if int(payload["block_size"]) != self.block_size:
            raise ValueError(
                f"payload block_size {payload['block_size']} != pool "
                f"block_size {self.block_size}")
        src_start = int(payload["start_block"])
        n = int(payload["blocks"])
        sb = src_start if start_block is None else int(start_block)
        off = sb - src_start
        if off < 0:
            raise ValueError(
                f"graft start {sb} precedes payload start {src_start}")
        with self._lock:
            table = self._tables[seq_id]
            dst = table[sb:src_start + n]
            if not dst:
                return 0
            for b in dst:
                if self._ref.get(b, 0) != 1 or b in self._block_key:
                    raise ValueError(
                        f"graft target block {b} is shared or "
                        f"registered — grafting would corrupt another "
                        f"sequence's context")
            import jax.numpy as jnp

            idx = jnp.asarray(np.asarray(dst, np.int32))
            sl = slice(off, off + len(dst))
            for pool, part in [(self.data, payload)] + [
                    (self._aux[a], p)
                    for a, p in payload.get("aux", {}).items()
                    if a in self._aux]:
                for name in ("k", "v"):
                    arr = jnp.asarray(part[name][:, sl],
                                      pool[name].dtype)
                    pool[name] = pool[name].at[:, idx].set(arr)
            self.blocks_grafted += len(dst)
            return len(dst)

    # -------------------------------------------------------- prefix cache
    def register_prefix(self, seq_id: int, upto_tokens: int) -> int:
        """Register ``seq_id``'s full prompt blocks covering
        ``[0, upto_tokens)`` as shareable (called by the engine after
        each prefill chunk lands, so a concurrent same-prefix request
        can hit blocks mid-prefill). Returns blocks newly registered."""
        if not self.enable_prefix_caching:
            return 0
        with self._lock:
            digests = self._prompt_digests.get(seq_id)
            if digests is None:
                return 0
            table = self._tables.get(seq_id, [])
            start = self._registered_upto.get(seq_id, 0)
            upto = min(upto_tokens // self.block_size, len(digests),
                       len(table))
            new = 0
            for i in range(start, upto):
                d = digests[i]
                b = table[i]
                if d in self._key_block or b in self._block_key:
                    continue  # another block is already canonical
                self._key_block[d] = b
                self._block_key[b] = d
                new += 1
            self._registered_upto[seq_id] = max(start, upto)
            return new

    def prefix_digest(self, limit: Optional[int] = None) -> List[str]:
        """Report of every registered chain digest (live and cached-
        free) — what a Serve replica publishes so the router can score
        it by cached-prefix overlap. Unbounded by default (at most
        ``usable_blocks`` entries); with ``limit``, the FIRST-registered
        digests are kept — registration runs prefix-to-tail, so a
        truncated report degrades long chains' tails, never their
        heads, and the router's leading-overlap scoring stays sound."""
        with self._lock:
            out = list(self._key_block.keys())
        return out if limit is None else out[:limit]

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    # -------------------------------------------------------------- queries
    def table(self, seq_id: int) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    def num_seqs(self) -> int:
        with self._lock:
            return len(self._tables)

    def padded_tables(self, seq_ids: List[int],
                      pad_len: Optional[int] = None) -> np.ndarray:
        """[B, M] int32 block-table batch, rows padded with NULL_BLOCK."""
        with self._lock:
            tables = [self._tables[s] for s in seq_ids]
        m = max((len(t) for t in tables), default=1)
        m = max(m, pad_len or 1)
        out = np.full((len(tables), m), NULL_BLOCK, np.int32)
        for i, t in enumerate(tables):
            out[i, :len(t)] = t
        return out

    def stats(self) -> Dict[str, int]:
        with self._lock:
            saved = self.prefill_tokens_saved
            seen = self.prefix_cache_query_tokens
            return {
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "usable_blocks": self.usable_blocks,
                "blocks_in_use": self.blocks_in_use,
                "free_blocks": self.free_blocks,
                "cached_free_blocks": len(self._cached_free),
                "peak_blocks_in_use": self.peak_blocks_in_use,
                "total_blocks_allocated": self.total_blocks_allocated,
                "total_blocks_freed": self.total_blocks_freed,
                "live_sequences": len(self._tables),
                "prefix_caching_enabled": int(self.enable_prefix_caching),
                "prefix_cache_queries": self.prefix_cache_queries,
                "prefix_cache_hits": self.prefix_cache_hits,
                "prefill_tokens_saved": saved,
                "prefix_cache_hit_rate": (saved / seen) if seen else 0.0,
                "cow_copies": self.cow_copies,
                "cached_blocks_evicted": self.cached_blocks_evicted,
                "blocks_exported": self.blocks_exported,
                "blocks_grafted": self.blocks_grafted,
                "aux_pools": list(self._aux),
            }
