"""LLM inference-engine tests (reference test model: vLLM's
test_scheduler/test_block_manager + Ray Serve LLM streaming tests —
paged-KV correctness against the cacheless forward pass, continuous-
batching parity with sequential decode, block accounting under
cancellation, and KV-full admission parking).

Engine-level tests run in-driver on the CPU backend (tiny f32 model,
GQA with n_kv_heads < n_heads so the grouped cache path is exercised);
Serve integration lives in test_serve.py (slow suite).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import (
    EngineConfig,
    EngineQueueFull,
    InferenceEngine,
    KVCacheOOM,
    PagedKVCache,
    Request,
    Scheduler,
)
from ray_tpu.models import (
    TransformerConfig,
    forward,
    init_kv_cache,
    init_params,
    prefill_chunk,
)
from ray_tpu.models.transformer import decode_step

MODEL = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=48, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_params(MODEL, jax.random.PRNGKey(0))


def _engine(params, **over):
    cfg = dict(model=MODEL, num_blocks=48, block_size=4, max_num_seqs=4,
               prefill_token_budget=256, max_queued_requests=16)
    cfg.update(over)
    return InferenceEngine(EngineConfig(**cfg), params=params)


# ---------------------------------------------------------------- model math
def test_paged_attention_decode_matches_dense():
    """ops-level: attention over a scattered paged cache == dense
    attention over the contiguous context, with GQA kept grouped."""
    from ray_tpu.ops.paged_attention import paged_attention_decode

    key = jax.random.PRNGKey(1)
    B, Hq, Hkv, Dh, bs = 3, 4, 2, 8, 4
    ctx_lens = np.array([5, 9, 2], np.int32)
    n_blocks = 16
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, Dh), jnp.float32)
    k_ctx = jax.random.normal(kk, (B, 12, Hkv, Dh), jnp.float32)
    v_ctx = jax.random.normal(kv, (B, 12, Hkv, Dh), jnp.float32)

    # Scatter each sequence's context into non-contiguous blocks.
    rng = np.random.default_rng(0)
    k_cache = np.zeros((n_blocks, bs, Hkv, Dh), np.float32)
    v_cache = np.zeros((n_blocks, bs, Hkv, Dh), np.float32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    tables = np.zeros((B, 3), np.int32)
    for b in range(B):
        n_blk = -(-int(ctx_lens[b]) // bs)
        blocks = [free.pop() for _ in range(n_blk)]
        tables[b, :n_blk] = blocks
        for pos in range(int(ctx_lens[b])):
            k_cache[blocks[pos // bs], pos % bs] = k_ctx[b, pos]
            v_cache[blocks[pos // bs], pos % bs] = v_ctx[b, pos]

    out = paged_attention_decode(
        q, jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables), jnp.asarray(ctx_lens))

    # Dense reference with repeat-expanded heads.
    for b in range(B):
        L = int(ctx_lens[b])
        k = np.repeat(k_ctx[b, :L], Hq // Hkv, axis=1)  # [L, Hq, Dh]
        v = np.repeat(v_ctx[b, :L], Hq // Hkv, axis=1)
        s = np.einsum("hd,lhd->hl", np.asarray(q[b]), k) * Dh ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hl,lhd->hd", p, v)
        np.testing.assert_allclose(np.asarray(out[b]), ref, atol=1e-5)


def test_grouped_gqa_dense_attention_matches_repeat():
    """Satellite: the non-flash dense path computes GQA in grouped form;
    it must equal the old repeat-expanded formulation exactly."""
    from ray_tpu.models.transformer import _attention_dense

    key = jax.random.PRNGKey(2)
    B, S, Hq, Hkv, Dh = 2, 6, 8, 2, 4
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, Hq, Dh), jnp.float32)
    k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32)
    out = _attention_dense(q, k, v, causal=True)

    k_rep = jnp.repeat(k, Hq // Hkv, axis=2).transpose(0, 2, 1, 3)
    v_rep = jnp.repeat(v, Hq // Hkv, axis=2).transpose(0, 2, 1, 3)
    qT = q.transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", qT, k_rep) * (Dh ** -0.5)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", p, v_rep).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_prefill_and_decode_match_forward(params):
    """Paged prefill + single-token decode reproduce the cacheless
    forward pass logits (teacher-forced) and greedy tokens exactly."""
    prompt = [3, 17, 5, 9, 22]
    cache = init_kv_cache(MODEL, 16, 4)
    table = np.zeros((1, 4), np.int32)
    table[0, :3] = [7, 2, 11]  # deliberately non-contiguous
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = prompt
    logits, cache = prefill_chunk(
        MODEL, params, cache, jnp.asarray(toks), jnp.asarray([0]),
        jnp.asarray([5]), jnp.asarray(table))
    ref = forward(MODEL, params, jnp.asarray([prompt]))[0, -1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               atol=1e-5)

    seq = list(prompt)
    tok = int(jnp.argmax(logits[0]))
    got = [tok]
    for pos in range(5, 10):
        logits, cache = decode_step(
            MODEL, params, cache, jnp.asarray([tok]), jnp.asarray([pos]),
            jnp.asarray(table))
        tok = int(jnp.argmax(logits[0]))
        got.append(tok)
    want = []
    for _ in range(6):
        lg = forward(MODEL, params, jnp.asarray([seq]))[0, -1]
        t = int(jnp.argmax(lg))
        want.append(t)
        seq.append(t)
    assert got == want


# --------------------------------------------------------------- kv manager
def test_block_manager_allocate_free_accounting():
    cache = PagedKVCache(MODEL, num_blocks=9, block_size=4)
    assert cache.usable_blocks == 8  # block 0 is NULL
    assert cache.allocate(1, 10)     # 3 blocks
    assert cache.blocks_in_use == 3
    assert not cache.allocate(2, 40)  # 10 blocks > 5 free: parks, no grab
    assert cache.blocks_in_use == 3
    assert cache.ensure_slot(1, 12)  # grows to 4 blocks
    assert cache.blocks_in_use == 4
    table = cache.table(1)
    assert len(set(table)) == 4 and 0 not in table
    assert cache.free(1) == 4
    assert cache.blocks_in_use == 0
    assert cache.total_blocks_freed == 4
    assert cache.free(1) == 0  # idempotent


def test_scheduler_waitqueue_bound():
    cache = PagedKVCache(MODEL, num_blocks=9, block_size=4)
    sched = Scheduler(cache, max_queued_requests=2)
    sched.submit(Request([1], 4))
    sched.submit(Request([1], 4))
    with pytest.raises(EngineQueueFull):
        sched.submit(Request([1], 4))


# ----------------------------------------------------- acceptance (a): parity
def test_concurrent_requests_match_sequential_greedy(params):
    """N concurrent mixed-length requests complete with outputs
    token-for-token identical to one-at-a-time greedy decode."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9], [10, 11],
               [12, 13, 14, 15], [16, 17]]
    lens = [6, 9, 4, 8, 5, 7]
    engine = _engine(params)
    sequential = []
    for p, n in zip(prompts, lens):
        sequential.append(list(engine.generate(p, max_new_tokens=n)))
        assert engine.wait_idle(30)

    concurrent = [None] * len(prompts)

    def consume(i):
        concurrent[i] = list(
            engine.generate(prompts[i], max_new_tokens=lens[i]))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert concurrent == sequential
    st = engine.stats()
    assert st["blocks_in_use"] == 0 and st["running"] == 0
    engine.shutdown()


def _poll(predicate, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


# -------------------------------------------- acceptance (b): close() frees
def test_close_frees_blocks_and_admits_waiting(params):
    """Mid-generation close() releases the sequence's KV blocks (by the
    accounting counters) and a parked request is admitted and runs."""
    # Pool sized so the hog's full completion fits; its budget is large
    # enough that it cannot finish before the close below.
    engine = _engine(params, max_num_seqs=1, num_blocks=300)
    hog = engine.generate([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=1000)
    assert next(hog) is not None
    st = engine.stats()
    hog_blocks = st["blocks_in_use"]
    freed_before = st["total_blocks_freed"]
    assert hog_blocks > 0 and st["running"] == 1

    got = {}
    waiter = threading.Thread(target=lambda: got.setdefault(
        "out", list(engine.generate([9, 8, 7], max_new_tokens=4))))
    waiter.start()
    assert _poll(lambda: engine.stats()["waiting"] == 1), \
        "second request should park (seq cap)"
    assert "out" not in got
    assert engine.stats()["running"] == 1, "hog finished too early"

    hog.close()
    waiter.join(30)
    assert got.get("out") is not None and len(got["out"]) == 4
    assert _poll(lambda: engine.stats()["blocks_in_use"] == 0)
    st = engine.stats()
    assert st["total_blocks_freed"] >= freed_before + hog_blocks
    assert st["running"] == 0
    engine.shutdown()


# ----------------------------------------- acceptance (c): KV-full parking
def _drain(req, timeout_s=60.0):
    """Read one request's streamed tokens to completion."""
    from ray_tpu.llm.engine import _ERROR

    out = []
    while True:
        item = req.output_queue.get(timeout=timeout_s)
        if isinstance(item, tuple):
            kind, payload = item
            if kind == _ERROR:
                raise payload
            return out
        out.append(item)


def test_kv_full_admission_parks_and_resumes(params):
    """When the pool can't cover a prompt, admission PARKS the request
    (no crash) and resumes it once a finishing sequence frees blocks."""
    # 9 usable blocks of 4: r1 takes 6 at admission (prompt 20 + 1) and
    # grows to 7; r2 needs 4 — parked until r1's blocks come back.
    # Submitting both under the step lock pins one admission wave (FIFO:
    # r1 admits, r2 parks) regardless of compile-cache warmth.
    engine = _engine(params, num_blocks=10, max_num_seqs=4,
                     max_queued_requests=8)
    with engine._lock:
        r1 = engine.submit([1] * 20, max_new_tokens=8)
        r2 = engine.submit([2] * 12, max_new_tokens=4)
    assert _poll(lambda: engine.stats()["park_events"] >= 1), \
        "KV-full admission never parked"

    assert len(_drain(r1)) == 8   # r1 completes -> blocks free
    assert len(_drain(r2)) == 4   # -> r2 admitted and runs
    st = engine.stats()
    assert st["blocks_in_use"] == 0 and st["waiting"] == 0
    assert st["peak_blocks_in_use"] <= st["usable_blocks"]
    engine.shutdown()


def test_preempted_prompt_grown_past_budget_still_completes(params):
    """Regression: recompute-preemption can grow a request's effective
    prompt past prefill_token_budget; re-admission must run it solo
    instead of parking it at the FIFO head forever (engine livelock)."""
    engine = _engine(params, num_blocks=12, block_size=2, max_num_seqs=4,
                     prefill_token_budget=8, max_queued_requests=8)
    # Two 6-token prompts x 10 new tokens need 8 blocks each at full
    # length; the 11-block pool forces a mid-decode preemption, and the
    # victim's recompute prompt (6 + emitted > 8) exceeds the budget.
    with engine._lock:
        r1 = engine.submit([1] * 6, max_new_tokens=10)
        r2 = engine.submit([2] * 6, max_new_tokens=10)
    out1 = _drain(r1)
    out2 = _drain(r2)
    assert len(out1) == 10 and len(out2) == 10
    st = engine.stats()
    assert st["num_preempted"] >= 1, (
        "pool never pressured: the budget-growth path was not exercised")
    assert st["blocks_in_use"] == 0 and st["waiting"] == 0
    engine.shutdown()


def test_shutdown_cancels_and_drains_waitqueue(params):
    """Regression: shutdown() must remove queued requests from the
    waitqueue (not just mark them CANCELLED) so a racing step cannot
    re-admit them and reallocate KV blocks after the DONE sentinel."""
    engine = _engine(params, max_num_seqs=1)
    with engine._lock:
        reqs = [engine.submit([1, 2, 3], max_new_tokens=50)
                for _ in range(3)]
    engine.shutdown()
    assert engine.scheduler.queue_depth() == 0
    for r in reqs:
        _drain(r)  # DONE sentinel delivered, no error
        assert r.finished()
    assert _poll(lambda: engine.stats()["blocks_in_use"] == 0)
    assert engine.stats()["running"] == 0


def test_step_failure_fails_requests_typed_and_engine_recovers(params):
    """Regression: an unexpected exception inside step() must not kill
    the loop thread silently — in-flight requests fail TYPED (blocks
    freed) and the engine keeps serving subsequent submits."""
    engine = _engine(params)
    good_prefill = engine._prefill_chunk

    def boom(*a, **k):
        raise RuntimeError("poisoned step")

    engine._prefill_chunk = boom
    gen = engine.generate([1, 2, 3], max_new_tokens=4, timeout_s=30)
    with pytest.raises(RuntimeError, match="poisoned step"):
        next(gen)
    st = engine.stats()
    assert st["blocks_in_use"] == 0 and st["running"] == 0
    engine._prefill_chunk = good_prefill
    assert len(list(engine.generate([1, 2, 3], max_new_tokens=4))) == 4
    engine.shutdown()


def test_oversized_request_rejected_at_submit(params):
    engine = _engine(params, num_blocks=10)
    with pytest.raises(KVCacheOOM):
        engine.submit([1] * 8, max_new_tokens=500)
    with pytest.raises(ValueError):
        engine.submit([1] * 9999, max_new_tokens=1)
    engine.shutdown()


def test_preemption_recompute_keeps_tokens_consistent(params):
    """Force mid-decode preemption (pool too small for both completions)
    and check the evicted sequence's final output still matches its
    solo greedy decode — recompute resumes exactly."""
    engine = _engine(params, num_blocks=48)
    solo = {}
    for tag, p, n in (("a", [1, 2, 3, 4], 20), ("b", [5, 6, 7, 8], 20)):
        solo[tag] = list(engine.generate(p, max_new_tokens=n))
        assert engine.wait_idle(30)
    engine.shutdown()

    # 11 usable blocks; each request ultimately needs 6 — decode growth
    # must evict the younger sequence at least once.
    engine = _engine(params, num_blocks=12, max_queued_requests=8)
    got = {}

    def run(tag, p, n):
        got[tag] = list(engine.generate(p, max_new_tokens=n))

    ts = [threading.Thread(target=run, args=("a", [1, 2, 3, 4], 20)),
          threading.Thread(target=run, args=("b", [5, 6, 7, 8], 20))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert got["a"] == solo["a"]
    assert got["b"] == solo["b"]
    st = engine.stats()
    assert st["blocks_in_use"] == 0
    engine.shutdown()


# ------------------------------------------- serve streaming signal (unit)
class _StubRefGen:
    """Stands in for an ObjectRefGenerator: never yields, records close."""

    def __init__(self):
        self.closed = False

    def __next__(self):
        raise StopIteration

    def close(self):
        self.closed = True


def test_open_stream_counts_as_ongoing_request_until_closed():
    """Satellite: a DeploymentResponseGenerator holds its replica's
    in-flight slot while open — the autoscaling signal for streaming
    load — and releases exactly once on close()/exhaustion."""
    from ray_tpu.serve.handle import DeploymentResponseGenerator
    from ray_tpu.serve.router import ReplicaSet

    rs = ReplicaSet()
    replica = object()
    rs.update([replica])
    key, chosen = rs.choose()
    assert rs.queue_lengths() == [1]
    gen = DeploymentResponseGenerator(_StubRefGen(), rs, key,
                                      replica=chosen)
    # Held open (no consumption): still counted as ongoing.
    time.sleep(0.05)
    assert rs.queue_lengths() == [1]
    gen.close()
    assert rs.queue_lengths() == [0]
    assert gen._gen.closed
    gen.close()  # idempotent: no double decrement
    assert rs.queue_lengths() == [0]

    # Exhaustion also releases.
    key2, chosen2 = rs.choose()
    gen2 = DeploymentResponseGenerator(_StubRefGen(), rs, key2,
                                       replica=chosen2)
    assert rs.queue_lengths() == [1]
    with pytest.raises(StopIteration):
        next(gen2)
    assert rs.queue_lengths() == [0]


def test_failed_item_get_closes_stream_and_releases_slot():
    """Regression: when an item ref fails to materialize, the consumer
    must CANCEL the replica generator (close), not only release the
    router slot — otherwise the replica keeps generating unaccounted."""
    from ray_tpu.serve.handle import DeploymentResponseGenerator
    from ray_tpu.serve.router import ReplicaSet

    class _YieldingStub(_StubRefGen):
        def __next__(self):
            return object()  # ray_tpu.get on this raises (no runtime)

    rs = ReplicaSet()
    rs.update([object()])
    key, chosen = rs.choose()
    gen = DeploymentResponseGenerator(_YieldingStub(), rs, key,
                                      replica=chosen)
    with pytest.raises(Exception):
        next(gen)
    assert rs.queue_lengths() == [0]
    assert gen._gen.closed, "replica generator not cancelled on item loss"


def test_kv_fallback_stream_close_releases_slot():
    """Satellite: the thin-client KV fallback stream also stops counting
    as ongoing when closed/abandoned (it previously had no close path)."""
    from ray_tpu.serve.handle import _KVStreamFallbackGenerator
    from ray_tpu.serve.router import ReplicaSet

    class _StubRef:
        pass

    rs = ReplicaSet()
    rs.update([object()])
    key, _ = rs.choose()
    assert rs.queue_lengths() == [1]
    gen = _KVStreamFallbackGenerator(_StubRef(), rs, key, "stream-x")
    gen.close()
    assert rs.queue_lengths() == [0]
    gen.close()
    assert rs.queue_lengths() == [0]


# ===================================================================
# PR 7: prefix caching / chunked prefill / TP decode / prefix router
# ===================================================================

def test_paged_attention_prefill_matches_dense_reference():
    """ops-level: chunk attention over the paged cache (cached prefix +
    in-chunk causal in one position mask) == dense reference."""
    from ray_tpu.ops.paged_attention import paged_attention_prefill

    key = jax.random.PRNGKey(3)
    B, C, Hq, Hkv, Dh, bs = 2, 4, 4, 2, 8, 4
    total_lens = [10, 7]          # full context incl. the chunk
    starts = [6, 3]               # chunk covers [start, start+C)
    n_blocks = 16
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, C, Hq, Dh), jnp.float32)
    k_ctx = jax.random.normal(kk, (B, 12, Hkv, Dh), jnp.float32)
    v_ctx = jax.random.normal(kv, (B, 12, Hkv, Dh), jnp.float32)

    rng = np.random.default_rng(1)
    k_cache = np.zeros((n_blocks, bs, Hkv, Dh), np.float32)
    v_cache = np.zeros((n_blocks, bs, Hkv, Dh), np.float32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    tables = np.zeros((B, 3), np.int32)
    for b in range(B):
        n_blk = -(-int(total_lens[b]) // bs)
        blocks = [free.pop() for _ in range(n_blk)]
        tables[b, :n_blk] = blocks
        for pos in range(int(total_lens[b])):
            k_cache[blocks[pos // bs], pos % bs] = k_ctx[b, pos]
            v_cache[blocks[pos // bs], pos % bs] = v_ctx[b, pos]

    q_positions = np.array([[s + i for i in range(C)] for s in starts],
                           np.int32)
    out = paged_attention_prefill(
        q, jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables), jnp.asarray(q_positions))

    for b in range(B):
        for i in range(C):
            p = starts[b] + i
            if p >= total_lens[b]:
                continue  # padded tail rows are garbage by contract
            k = np.repeat(k_ctx[b, :p + 1], Hq // Hkv, axis=1)
            v = np.repeat(v_ctx[b, :p + 1], Hq // Hkv, axis=1)
            s = np.einsum("hd,lhd->hl", np.asarray(q[b, i]), k) * Dh ** -0.5
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            ref = np.einsum("hl,lhd->hd", pr, v)
            np.testing.assert_allclose(np.asarray(out[b, i]), ref,
                                       atol=1e-5)


def test_flash_attention_grouped_matches_expanded():
    """Satellite: the grouped GQA flash forward (kv block specs
    index-mapped per query head, no repeat-expanded K/V) must equal the
    repeat-expanded formulation — kernel path and fallback path."""
    from ray_tpu.ops.flash_attention import (
        _fallback,
        flash_attention_grouped,
    )

    key = jax.random.PRNGKey(4)
    B, Hq, Hkv, S, D = 2, 8, 2, 64, 16
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, Hkv, S, D), jnp.float32)
    k_rep = jnp.repeat(k, Hq // Hkv, axis=1)
    v_rep = jnp.repeat(v, Hq // Hkv, axis=1)
    for causal in (True, False):
        out = flash_attention_grouped(q, k, v, causal=causal,
                                      block_q=16, block_k=16,
                                      interpret=True)
        ref = _fallback(q, k_rep, v_rep, causal, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
    # Non-tileable shapes take the grouped dense fallback.
    out = flash_attention_grouped(q[:, :, :12], k[:, :, :12], v[:, :, :12],
                                  causal=True)
    ref = _fallback(q[:, :, :12], k_rep[:, :, :12], v_rep[:, :, :12],
                    True, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------------------ acceptance (a): prefix-cache skip
def test_prefix_cache_skips_shared_prefix_counter_asserted(params):
    """Two requests sharing a long prompt prefix produce greedy outputs
    token-for-token identical to the caching-disabled engine, while the
    second request's prefill computes ONLY the unshared tail."""
    prefix = list(range(1, 25))       # 24 tokens = 6 full blocks (bs 4)
    p1 = prefix + [30, 31, 32]
    p2 = prefix + [40, 41]

    ref_engine = _engine(params, enable_prefix_caching=False)
    ref1 = list(ref_engine.generate(p1, max_new_tokens=6))
    assert ref_engine.wait_idle(30)
    ref2 = list(ref_engine.generate(p2, max_new_tokens=6))
    assert ref_engine.wait_idle(30)
    ref_engine.shutdown()

    engine = _engine(params)
    out1 = list(engine.generate(p1, max_new_tokens=6))
    assert engine.wait_idle(30)
    computed_before = engine.num_prefill_tokens
    out2 = list(engine.generate(p2, max_new_tokens=6))
    assert engine.wait_idle(30)

    assert out1 == ref1
    assert out2 == ref2
    st = engine.stats()
    assert st["prefill_tokens_saved"] == len(prefix)
    assert st["prefix_cache_hits"] == 1
    # The second prefill computed exactly the unshared tail.
    assert engine.num_prefill_tokens - computed_before == len(p2) - 24
    assert st["blocks_in_use"] == 0
    engine.shutdown()


def test_fully_cached_prompt_copies_on_write(params):
    """A request whose ENTIRE prompt is cached still computes its last
    position (for logits) — writing into the final shared block, which
    must copy-on-write while the donor sequence keeps decoding on the
    original block, streams unaffected."""
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full blocks (bs 4)
    ref_engine = _engine(params, enable_prefix_caching=False)
    ref_a = list(ref_engine.generate(prompt, max_new_tokens=12))
    assert ref_engine.wait_idle(30)
    ref_b = list(ref_engine.generate(prompt, max_new_tokens=5))
    assert ref_engine.wait_idle(30)
    ref_engine.shutdown()

    engine = _engine(params, num_blocks=48)
    g1 = engine.generate(prompt, max_new_tokens=12)
    first = next(g1)  # prefill landed -> prompt blocks registered, live
    out2 = list(engine.generate(prompt, max_new_tokens=5))
    st = engine.stats()
    assert st["cow_copies"] >= 1, "shared tail block was not COW'd"
    assert st["prefill_tokens_saved"] == len(prompt) - 1
    out1 = [first] + list(g1)
    assert out1 == ref_a, "donor stream corrupted by the COW"
    assert out2 == ref_b
    assert _poll(lambda: engine.stats()["blocks_in_use"] == 0)
    engine.shutdown()


# ------------------------------------ acceptance (b): chunked prefill
def test_chunked_prefill_bounds_batch_stall(params):
    """A prompt far over the prefill token budget is admitted (no
    rejection) and prefills as several chunks across ITERATIONS — the
    running batch's inter-token stall is bounded by one chunk budget
    (counter-asserted) and decode keeps flowing between chunks."""
    budget = 8
    long_prompt = list(range(1, 33))   # 32 tokens = 4 chunks of 8
    engine = _engine(params, prefill_token_budget=budget, num_blocks=64)
    short = engine.submit([9, 8, 7], max_new_tokens=30)
    assert _poll(lambda: len(short.out_tokens) >= 2)
    r_long = engine.submit(long_prompt, max_new_tokens=4)
    out_long = _drain(r_long)
    out_short = _drain(short)
    assert len(out_long) == 4 and len(out_short) == 30
    st = engine.stats()
    assert st["max_prefill_tokens_per_step"] <= budget
    assert st["prefill_chunks_scheduled"] >= 5  # short + 4 long chunks
    assert st["coscheduled_steps"] >= 3, (
        "decode stalled while the long prompt prefilled")
    engine.shutdown()

    # Parity: chunked prefill changes WHEN tokens compute, never WHAT
    # they are — same greedy outputs as a one-shot prefill.
    ref_engine = _engine(params, prefill_token_budget=256,
                         enable_prefix_caching=False)
    assert list(ref_engine.generate(long_prompt, max_new_tokens=4)) == \
        out_long
    ref_engine.shutdown()


# ------------------------------------ acceptance (c): TP decode parity
def test_tp_decode_matches_single_device(params):
    """Tensor-parallel decode over the mesh (params column/row sharded,
    KV cache sharded along n_kv_heads) produces token-for-token
    identical greedy outputs to the single-device engine."""
    prompts = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10, 11, 12, 13]]
    outs = {}
    for tp in (1, 2):
        engine = _engine(params, tp_size=tp, enable_prefix_caching=False)
        if tp > 1:
            assert engine.mesh is not None
        outs[tp] = []
        for p in prompts:
            outs[tp].append(list(engine.generate(p, max_new_tokens=10)))
            assert engine.wait_idle(60)
        engine.shutdown()
    assert outs[1] == outs[2], "TP decode diverged from single-device"


def test_tp_prefill_and_decode_logits_close(params):
    """Program-level TP check: the sharded prefill_chunk + decode_step
    produce logits matching the unsharded programs."""
    from ray_tpu.llm.engine import InferenceEngine as IE
    from ray_tpu.models import init_kv_cache, prefill_chunk
    from ray_tpu.models.transformer import decode_step
    from ray_tpu.parallel.sharding import kv_cache_specs, shard_params
    from jax.sharding import NamedSharding

    mesh, rules = IE._build_tp_mesh(2)
    from ray_tpu.models import param_specs

    sharded = shard_params(params, mesh, param_specs(MODEL, rules))
    specs = kv_cache_specs(rules)

    prompt = [3, 17, 5, 9, 22, 11]
    table = np.zeros((1, 4), np.int32)
    table[0, :2] = [5, 9]
    toks = np.zeros((1, 8), np.int32)
    toks[0, :6] = prompt

    def run(p, cache, mesh_, rules_):
        lg, cache = prefill_chunk(
            MODEL, p, cache, jnp.asarray(toks), jnp.asarray([0]),
            jnp.asarray([6]), jnp.asarray(table), mesh=mesh_,
            rules=rules_)
        tok = int(np.argmax(np.asarray(lg[0])))
        lg2, cache = decode_step(
            MODEL, p, cache, jnp.asarray([tok]), jnp.asarray([6]),
            jnp.asarray(table), mesh=mesh_, rules=rules_)
        return np.asarray(lg[0]), np.asarray(lg2[0])

    base1, base2 = run(params, init_kv_cache(MODEL, 16, 4), None, None)
    import jax as _jax

    cache_tp = {
        k: _jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in init_kv_cache(MODEL, 16, 4).items()
    }
    tp1, tp2 = run(sharded, cache_tp, mesh, rules)
    np.testing.assert_allclose(tp1, base1, atol=1e-5)
    np.testing.assert_allclose(tp2, base2, atol=1e-5)


# --------------------------- satellite: shared-block lifecycle churn
def test_shared_block_refcount_lifecycle_unit():
    """Cache-level churn proof: freeing a sequence that shares prefix
    blocks frees ONLY its private blocks; zero-ref registered blocks
    park in the cached-free tier; a reclaimed block's digest entries
    are gone, so a racing admit can never resurrect it."""
    cache = PagedKVCache(MODEL, num_blocks=32, block_size=4)
    prompt = list(range(1, 18))  # 17 tokens: 4 full blocks + tail
    assert cache.allocate_prefix(1, prompt) == 0  # cold cache
    cache.register_prefix(1, len(prompt))
    assert cache.allocate_prefix(2, prompt) == 16
    t1, t2 = cache.table(1), cache.table(2)
    assert t1[:4] == t2[:4], "leading full blocks should be shared"
    assert t1[4:] != t2[4:]
    for b in t1[:4]:
        assert cache.refcount(b) == 2
    # Mid-decode close of seq 2: only its private block(s) free.
    private_2 = len(t2) - 4
    assert cache.free(2) == private_2
    for b in t1[:4]:
        assert cache.refcount(b) == 1, "shared block freed with seq 2"
    # Recompute-preemption analogue for seq 1 (same release path): its
    # registered blocks PARK in cached-free, still matchable.
    cache.free(1)
    assert cache.blocks_in_use == 0
    assert cache.cached_free_blocks == 4
    assert cache.allocate_prefix(3, prompt) == 16  # hit from cached-free
    cache.free(3)
    # Reclaim the whole pool -> cached blocks evicted + deregistered.
    assert cache.allocate(4, 31 * 4)
    assert cache.stats()["cached_blocks_evicted"] == 4
    cache.free(4)
    # Racing admit after reclamation: the old digests must NOT match.
    hits_before = cache.prefix_cache_hits
    assert cache.allocate_prefix(5, prompt) == 0
    assert cache.prefix_cache_hits == hits_before, (
        "reclaimed block resurrected via a stale digest")


def test_close_with_shared_prefix_keeps_donor_stream_intact(params):
    """Engine-level churn: closing a sequence that shares prefix blocks
    with a live one must not disturb the donor's tokens, and the shared
    blocks must survive (parked, not leaked) after both are gone."""
    prefix = list(range(1, 17))  # 4 full blocks
    ref_engine = _engine(params, enable_prefix_caching=False)
    ref = list(ref_engine.generate(prefix + [21], max_new_tokens=12))
    assert ref_engine.wait_idle(30)
    ref_engine.shutdown()

    engine = _engine(params, num_blocks=64)
    g1 = engine.generate(prefix + [21], max_new_tokens=12)
    first = next(g1)
    g2 = engine.generate(prefix + [22], max_new_tokens=40)
    next(g2)
    assert engine.stats()["prefill_tokens_saved"] >= len(prefix)
    g2.close()  # mid-decode: frees only g2's private blocks
    out1 = [first] + list(g1)
    assert out1 == ref, "donor stream corrupted by sharer's close()"
    assert _poll(lambda: engine.stats()["blocks_in_use"] == 0)
    st = engine.stats()
    assert st["cached_free_blocks"] >= 4  # shared prefix parked for reuse
    engine.shutdown()


# ------------------------------------- satellite: prefix-aware router
def test_prefix_router_prefers_cached_replica():
    """Router unit: the replica whose digest report overlaps the
    request's prompt prefix wins — until it is overloaded past the
    locality slack, when power-of-two takes back over."""
    from ray_tpu.llm.kv_cache import chain_digests
    from ray_tpu.serve.router import PREFIX_LOAD_SLACK, ReplicaSet

    a, b = object(), object()
    rs = ReplicaSet()
    rs.update([a, b])
    prompt = list(range(64))
    digs = chain_digests(prompt, 4)
    rs.update_prefix_digest(id(b), 4, digs)

    keys = []
    for i in range(PREFIX_LOAD_SLACK + 1):
        key, r = rs.choose(prefix_tokens=prompt)
        assert r is b, f"cache-affinity choice {i} missed"
        keys.append(key)
    assert rs.prefix_routed == PREFIX_LOAD_SLACK + 1
    assert rs.prefix_overlap_tokens == (PREFIX_LOAD_SLACK + 1) * 64
    # b now carries slack+1 in-flight vs a's 0: locality must yield.
    key, r = rs.choose(prefix_tokens=prompt)
    assert r is a, "overloaded cached replica not load-balanced away"
    for k in keys + [key]:
        rs.release(k)
    # Longest contiguous overlap wins; a gap stops the chain.
    rs.update_prefix_digest(id(a), 4, [digs[0], digs[2]])
    key, r = rs.choose(prefix_tokens=prompt)
    assert r is b
    rs.release(key)
    # No overlap at all -> plain pow-2 (never raises).
    key, r = rs.choose(prefix_tokens=[999] * 16)
    rs.release(key)
    assert rs.prefix_routed == PREFIX_LOAD_SLACK + 2


def test_prefix_router_handle_extraction():
    """The handle only attempts prompt extraction for LLM-shaped
    requests; everything else routes exactly as before."""
    from ray_tpu.serve.handle import _extract_prefix_tokens

    assert _extract_prefix_tokens(([1, 2, 3],), {}) == [1, 2, 3]
    assert _extract_prefix_tokens(
        ({"prompt": [4, 5], "max_new_tokens": 2},), {}) == [4, 5]
    assert _extract_prefix_tokens(({"text": "hi"},), {}) is None
    assert _extract_prefix_tokens(("hello",), {}) is None
    assert _extract_prefix_tokens((), {}) is None
    assert _extract_prefix_tokens(([1, "x"],), {}) is None


# -------------------------------------- satellite: engine observability
def test_llm_engine_observability_state_and_dashboard(params):
    """util/state.list_llm_engines + the dashboard /api/llm endpoint
    expose the scheduler + prefix-cache counters live."""
    import json as _json
    import urllib.request

    from ray_tpu import dashboard as dash_mod
    from ray_tpu.util.state import list_llm_engines, summarize_llm_engines

    engine = _engine(params)
    prompt = list(range(1, 10))
    assert len(list(engine.generate(prompt, max_new_tokens=4))) == 4
    assert engine.wait_idle(30)
    list(engine.generate(prompt, max_new_tokens=4))  # prefix hit

    rows = [e for e in list_llm_engines()
            if e.engine_id == engine.engine_id]
    assert rows, "engine missing from util/state listing"
    st = rows[0]
    assert st.generated_tokens >= 8
    assert st.prefix_cache_hits >= 1
    assert st.prefill_tokens_saved >= 8
    assert st.prefix_cache_hit_rate > 0
    roll = summarize_llm_engines()
    assert roll["num_engines"] >= 1
    assert roll["prefill_tokens_saved"] >= 8

    dash = dash_mod.Dashboard(port=0)
    try:
        raw = urllib.request.urlopen(dash.url + "/api/llm",
                                     timeout=10).read()
        data = _json.loads(raw)
        mine = [e for e in data if e["engine_id"] == engine.engine_id]
        assert mine and mine[0]["prefix_cache_hits"] >= 1
    finally:
        dash.shutdown()
    engine.shutdown()


# -------------------- disaggregated prefill/decode (engine-level, PR 19)
def _drain_finished(req, timeout=30):
    """Consume one request's output queue to completion; returns the
    token list. The stream contract is uniform: tokens, then the
    (_DONE, status) sentinel — adopted requests included."""
    out = []
    while True:
        item = req.output_queue.get(timeout=timeout)
        if isinstance(item, tuple):
            kind, payload = item
            if kind == "__error__":
                raise payload
            assert kind == "__done__" and payload == "FINISHED", item
            return out
        out.append(item)


def test_hold_after_prefill_and_release_accounting(params):
    """A held sequence keeps its KV resident past FINISHED (the
    prefill-pool publish window); release_held frees it, idempotently,
    and shutdown sweeps whatever is still held."""
    engine = _engine(params)
    prompt = list(range(1, 9))
    req = engine.submit(prompt, max_new_tokens=1,
                        hold_after_prefill=True)
    first = req.output_queue.get(timeout=30)
    assert isinstance(first, int)
    assert req.output_queue.get(timeout=30) == ("__done__", "FINISHED")
    assert engine.held_count() == 1
    assert engine.stats()["held_sequences"] == 1
    held_blocks = engine.cache.stats()["blocks_in_use"]
    assert held_blocks > 0, "held sequence freed its KV"
    # The held KV really is the finished prefill: exporting it works.
    payload = engine.cache.export_blocks(req.seq_id, start_block=0)
    assert payload["blocks"] > 0
    assert engine.release_held(req.seq_id) > 0
    assert engine.release_held(req.seq_id) == 0  # idempotent
    assert engine.held_count() == 0
    assert engine.cache.stats()["blocks_in_use"] == 0
    # Shutdown sweep: a still-held sequence does not leak at teardown.
    req2 = engine.submit(prompt, max_new_tokens=1,
                         hold_after_prefill=True)
    _drain_finished(req2)
    assert engine.held_count() == 1
    engine.shutdown()
    assert engine.held_count() == 0


def test_kv_export_graft_adopt_continuation_parity(params):
    """The disagg hop at engine level: prefill on engine A (held),
    export blocks, adopt on engine B (graft + commit), stream — the
    decode-side tokens must equal a colocated run of the same request.
    Covers the full-ship, cached-prefix, and tail-only-ship paths, and
    asserts zero leaked blocks on both sides."""
    pre, dec, base = _engine(params), _engine(params), _engine(params)
    prompt = [5, 6, 7, 8, 9, 10, 11]
    ref = list(base.generate(prompt, max_new_tokens=8))
    base.shutdown()

    # Full ship: decode side has nothing cached.
    held = pre.submit(prompt, max_new_tokens=1, hold_after_prefill=True)
    first = held.output_queue.get(timeout=30)
    assert held.output_queue.get(timeout=30)[1] == "FINISHED"
    payload = pre.cache.export_blocks(held.seq_id, start_block=0)
    areq = dec.begin_adopted(prompt, max_new_tokens=8)
    assert areq is not None and areq.cached_prompt_tokens == 0
    assert dec.adopt_kv(areq, payload)
    blocks, nbytes = areq.kv_ship
    assert blocks == payload["blocks"] and nbytes > 0
    dec.commit_adopted(areq, first)
    assert _drain_finished(areq) == ref
    decomp = dec.ttft_decomposition()
    assert decomp["transfer_p50_s"] is not None
    assert decomp["transfer_p50_s"] >= 0

    # Cached-prefix adoption: the same prompt again — begin_adopted
    # finds the registered prefix, so the graft starts past it.
    areq2 = dec.begin_adopted(prompt, max_new_tokens=8)
    assert areq2 is not None and areq2.cached_prompt_tokens > 0
    assert dec.adopt_kv(areq2, payload)
    dec.commit_adopted(areq2, first)
    assert _drain_finished(areq2) == ref

    # Tail-only ship: export FROM the decode side's cached boundary —
    # the wire carries strictly fewer blocks than the full payload.
    held3 = pre.submit(prompt, max_new_tokens=1,
                       hold_after_prefill=True)
    f3 = held3.output_queue.get(timeout=30)
    held3.output_queue.get(timeout=30)
    areq3 = dec.begin_adopted(prompt, max_new_tokens=8)
    graft_from = areq3.cached_prompt_tokens // dec.cache.block_size
    assert graft_from > 0
    tail = pre.cache.export_blocks(held3.seq_id,
                                   start_block=graft_from)
    assert tail["blocks"] < payload["blocks"]
    pre.release_held(held3.seq_id)
    assert dec.adopt_kv(areq3, tail)
    dec.commit_adopted(areq3, f3)
    assert _drain_finished(areq3) == ref

    pre.release_held(held.seq_id)
    assert dec.wait_idle(30)
    assert pre.cache.stats()["blocks_in_use"] == 0
    assert dec.cache.stats()["blocks_in_use"] == 0
    assert pre.cache.stats()["blocks_exported"] > 0
    assert dec.cache.stats()["blocks_grafted"] > 0
    pre.shutdown()
    dec.shutdown()


def test_adopt_kv_refuses_stale_plan_and_aborts_clean(params):
    """A payload exported past the decode side's actual cached boundary
    (stale tail-skip plan) is REFUSED — adopt_kv returns False, the
    caller aborts, and nothing leaks."""
    pre, dec = _engine(params), _engine(params)
    prompt = [5, 6, 7, 8, 9, 10, 11]
    held = pre.submit(prompt, max_new_tokens=1, hold_after_prefill=True)
    held.output_queue.get(timeout=30)
    held.output_queue.get(timeout=30)
    payload = pre.cache.export_blocks(held.seq_id, start_block=1)
    areq = dec.begin_adopted(prompt, max_new_tokens=8)
    assert areq is not None
    # Decode side caches nothing -> graft boundary 0 < start_block 1.
    assert not dec.adopt_kv(areq, payload)
    dec.abort_adopted(areq)
    assert dec.cache.stats()["blocks_in_use"] == 0
    assert dec.stats()["running"] == 0
    pre.release_held(held.seq_id)
    assert pre.cache.stats()["blocks_in_use"] == 0
    pre.shutdown()
    dec.shutdown()


def test_publish_ttl_expiry_zero_leak(params, ray_start_regular,
                                      monkeypatch):
    """A publication never acked (decode replica died before pulling)
    expires on the TTL deadline: counters record it and the held KV
    blocks are freed — the publish/ack lifecycle cannot leak."""
    monkeypatch.setenv("RAY_TPU_LLM_KV_PUBLISH_TTL_S", "0.2")
    from ray_tpu.llm.disagg import PrefillLLMServer

    ps = PrefillLLMServer(
        EngineConfig(model=MODEL, num_blocks=48, block_size=4,
                     max_num_seqs=4), params=params)
    try:
        ticket = ps.prefill({"prompt": [3, 4, 5, 6, 7],
                             "max_new_tokens": 8})
        st = ps.stats()
        assert st["kv_publishes"] == 1
        assert st["kv_publications_outstanding"] == 1
        assert st["blocks_in_use"] > 0
        time.sleep(0.25)
        freed = ps.expire_published()
        assert freed > 0
        st = ps.stats()
        assert st["kv_expiries"] == 1
        assert st["kv_blocks_expired"] > 0
        assert st["kv_publications_outstanding"] == 0
        assert st["blocks_in_use"] == 0
        assert st["held_sequences"] == 0
        # A late ack (the decode side finally pulled a dead ticket) is
        # an idempotent no-op, not a double free.
        assert ps.ack(ticket["pub_id"]) == 0
        assert ps.stats()["kv_acks"] == 0
    finally:
        ps.engine.shutdown()


# --------------------------------- speculative decoding (PR 19)
def test_spec_decode_greedy_parity_across_pow2_buckets(params):
    """Speculative decoding is an EXACT greedy transform: with a draft
    that mostly disagrees (independent random weights), every batch
    bucket (1, 2, 4 = pow2 pads of 1/2/3 concurrent requests) must
    produce token-for-token the vanilla engine's output."""
    from ray_tpu.models import draft_config

    vanilla = _engine(params)
    spec = _engine(params, spec_k=3, draft_model=draft_config(MODEL))
    prompts = [[1 + (5 * i + j) % 60 for j in range(3 + 2 * i)]
               for i in range(3)]
    refs = [list(vanilla.generate(p, max_new_tokens=10))
            for p in prompts]
    for batch in (1, 2, 3):
        with spec._lock:
            reqs = [spec.submit(p, max_new_tokens=10)
                    for p in prompts[:batch]]
        assert spec.wait_idle(60)
        for req, ref in zip(reqs, refs):
            assert list(req.out_tokens) == ref, (
                f"spec decode diverged at batch {batch}")
    st = spec.stats()["spec"]
    assert st["rounds"] > 0 and st["proposed"] > 0
    assert 0.0 <= st["acceptance_rate"] < 1.0  # random draft: low
    # Each round emits, per batch row, its accepted run + 1 bonus: the
    # token total sits between the bonus floor and the per-row cap.
    assert st["rounds"] <= st["emitted"] <= \
        st["accepted"] + st["rounds"] * len(prompts)
    vanilla.shutdown()
    spec.shutdown()


def test_spec_decode_shift_pair_accepts_everything():
    """Acceptance-rate counters: a draft/flagship pair that agree by
    construction (synthetic shift models — greedy next token is
    (t + 1) % vocab for both) accept every proposal, and each round
    emits k accepted + 1 bonus token."""
    from ray_tpu.models import (TransformerConfig as TC, draft_config,
                                shift_params)

    cfg = TC(vocab_size=16, d_model=32, n_layers=2, n_heads=4,
             n_kv_heads=2, d_ff=48, dtype=jnp.float32)
    dcfg = draft_config(cfg)
    k = 3
    spec = InferenceEngine(
        EngineConfig(model=cfg, num_blocks=48, block_size=4,
                     max_num_seqs=2, spec_k=k, draft_model=dcfg),
        params=shift_params(cfg, shift=1),
        draft_params=shift_params(dcfg, shift=1))
    out = list(spec.generate([3], max_new_tokens=12))
    assert out == [(3 + 1 + i) % 16 for i in range(12)]
    st = spec.stats()["spec"]
    assert st["acceptance_rate"] == 1.0
    assert st["accepted"] == st["proposed"]
    assert st["fallback_rounds"] == 0
    spec.shutdown()


def test_spec_decode_fallback_to_vanilla(params):
    """spec_k=0 or a missing draft model disarm speculation entirely
    (no 'spec' stats key, plain decode path); a sampled request on an
    armed engine falls back PER ROUND and still matches the vanilla
    engine's sampled stream seed-for-seed."""
    from ray_tpu.models import draft_config

    # Disarmed: spec_k=0 even with a draft model present.
    e0 = _engine(params, spec_k=0, draft_model=draft_config(MODEL))
    assert "spec" not in e0.stats()
    # Disarmed: spec_k>0 but no draft model.
    e1 = _engine(params, spec_k=3)
    assert "spec" not in e1.stats()
    ref = list(e0.generate([2, 3, 4], max_new_tokens=6))
    assert list(e1.generate([2, 3, 4], max_new_tokens=6)) == ref
    e0.shutdown()
    e1.shutdown()

    # Armed engine, sampled request: per-round fallback, seeded parity.
    vanilla = _engine(params)
    spec = _engine(params, spec_k=3, draft_model=draft_config(MODEL))
    want = list(vanilla.generate([7, 8, 9], max_new_tokens=8,
                                 temperature=0.7, seed=123))
    got = list(spec.generate([7, 8, 9], max_new_tokens=8,
                             temperature=0.7, seed=123))
    assert got == want
    assert spec.stats()["spec"]["fallback_rounds"] > 0
    vanilla.shutdown()
    spec.shutdown()
