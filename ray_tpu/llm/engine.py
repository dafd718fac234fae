"""Continuous-batching inference engine over the flagship Transformer
(reference role: vLLM's LLMEngine / Ray Serve LLM's engine actor).

One ``InferenceEngine`` owns a paged KV cache pool (with copy-on-write
shared prefix blocks), a continuous-batching scheduler (with chunked
prefill), and two jitted programs over ``models.transformer``:

- ``prefill_chunk``: prompt slices, padded to a (batch, chunk) bucket,
  write their K/V into their allocated blocks in one program; a slice
  that completes its prompt produces the request's FIRST generated
  token. A prompt whose leading blocks hit the prefix cache starts its
  first chunk at the cached length — the shared tokens are never
  recomputed (``prefill_tokens_saved``). A prompt longer than the
  prefill token budget runs as several chunks across iterations, so
  the running batch's inter-token stall is bounded by one chunk.
- ``decode_step``: every fully-prefilled sequence advances one token
  per iteration in one program — Orca's iteration-level batching, so a
  new request joins the batch at the next step boundary instead of
  waiting for the batch to drain, and a finished sequence leaves it
  (and drops its block refs) immediately.

Tensor parallelism (``EngineConfig.tp_size``): the Megatron recipe from
``parallel/`` grafts onto both programs — per-layer weights column/row
sharded on the tp mesh axis, the KV pool sharded along ``n_kv_heads``
(each chip holds its head shard's blocks; block IDS stay global), GSPMD
inserting the psums — so model + cache scale past one chip while the
host-side scheduler and block manager are unchanged. TP decode is
asserted token-for-token identical to single-device decode.

Padding buckets are powers of two, so the number of distinct compiled
programs is logarithmic in the caps. Padded rows aim at the NULL block
and their logits are ignored; because attention masks every slot past a
sequence's context length, a sequence's tokens are IDENTICAL whatever
batch it happened to share an iteration with — the engine's
concurrent-equals-sequential parity test pins exactly that.

Requests stream: ``generate()`` yields token ids as iterations commit
them (time-to-first-token ≈ one prefill — one TAIL chunk when the
prefix cache hits), and closing the consumer (``GeneratorExit``)
cancels the sequence — its private blocks return to the pool
immediately (shared prefix blocks stay with their other holders),
unblocking parked admissions. The engine is thread-safe; a Serve
replica drives it from concurrent streaming handlers with no extra
locking.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ray_tpu.llm.kv_cache import KVCacheOOM, PagedKVCache  # noqa: F401
from ray_tpu.exceptions import RequestSheddedError
from ray_tpu.llm.scheduler import (
    CANCELLED,
    FAILED,
    FINISHED,
    SHED,
    EngineQueueFull,
    Request,
    Scheduler,
)

__all__ = ["EngineConfig", "InferenceEngine", "live_engines"]

_DONE = "__done__"
_ERROR = "__error__"

# Live engines in this process, for util/state + the dashboard (weak:
# observability must never keep a dead engine's KV pool alive).
_ENGINES: "weakref.WeakValueDictionary[int, InferenceEngine]" = \
    weakref.WeakValueDictionary()
_engine_ids = iter(range(1, 1 << 62))


def live_engines() -> List["InferenceEngine"]:
    """Engines constructed in this process and not yet GC'd (shutdown
    engines remain listed until collected — their final counters are
    still readable)."""
    return [e for _, e in sorted(_ENGINES.items())]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine knobs. ``model`` is the flagship TransformerConfig; the
    KV pool holds ``num_blocks`` blocks of ``block_size`` tokens each
    (block 0 reserved), shared by every live sequence."""

    model: Any = None                  # models.TransformerConfig
    num_blocks: int = 128
    block_size: int = 16
    max_num_seqs: int = 8              # iteration batch cap
    prefill_token_budget: int = 2048   # prompt tokens computed per step
    max_queued_requests: int = 64      # bounded waitqueue (admission)
    eos_token_id: Optional[int] = None
    max_new_tokens_default: int = 64
    param_seed: int = 0
    cache_dtype: Any = None            # default: model dtype
    enable_prefix_caching: bool = True  # COW shared prefix blocks
    tp_size: int = 1                   # tensor-parallel mesh width
    # Speculative decoding: a small DRAFT model proposes spec_k tokens
    # per iteration and the flagship verifies them in ONE multi-token
    # step (models.verify_step). spec_k=0 or draft_model=None disables
    # it (vanilla decode). The draft's KV rides the same block tables
    # as an aux pool. Greedy-only: a decode round containing any
    # temperature>0 sequence falls back to vanilla for that round.
    spec_k: int = 0
    draft_model: Any = None            # draft TransformerConfig

    def resolved_model(self):
        if self.model is not None:
            return self.model
        from ray_tpu.models import TransformerConfig

        return TransformerConfig()


def _pow2_at_least(n: int, floor: int = 1) -> int:
    m = max(int(n), floor)
    p = 1
    while p < m:
        p *= 2
    return p


class InferenceEngine:
    """See module docstring. Construct with real ``params`` or let the
    engine init them from ``param_seed`` (every Serve replica of one
    deployment then serves identical weights with zero shipping)."""

    def __init__(self, config: Optional[EngineConfig] = None,
                 params: Optional[dict] = None,
                 draft_params: Optional[dict] = None):
        import jax
        from functools import partial

        from ray_tpu.models import (
            decode_step,
            init_params,
            prefill_chunk,
            verify_step,
        )
        from ray_tpu.ops import backend as ops_backend
        from ray_tpu.parallel.sharding import (
            param_sharding_tree,
            shard_params,
        )

        self.config = config or EngineConfig()
        self.model_cfg = self.config.resolved_model()
        self.mesh = None
        rules = None
        if self.config.tp_size > 1:
            self.mesh, rules = self._build_tp_mesh(self.config.tp_size)
        if params is None:
            init = partial(init_params, self.model_cfg)
            if self.mesh is not None:
                # Born sharded: no device ever holds the whole tree.
                init = jax.jit(init, out_shardings=param_sharding_tree(
                    self.mesh, self._param_specs(rules)))
            params = init(jax.random.PRNGKey(self.config.param_seed))
        elif self.mesh is not None:
            params = shard_params(params, self.mesh,
                                  self._param_specs(rules))
        self.params = params
        self.cache = PagedKVCache(
            self.model_cfg, self.config.num_blocks, self.config.block_size,
            dtype=self.config.cache_dtype,
            enable_prefix_caching=self.config.enable_prefix_caching,
            mesh=self.mesh, rules=rules)
        self.scheduler = Scheduler(
            self.cache,
            max_num_seqs=self.config.max_num_seqs,
            prefill_token_budget=self.config.prefill_token_budget,
            max_queued_requests=self.config.max_queued_requests)
        # Donation rewrites the cache in place on accelerators; the CPU
        # backend only warns, so skip it there to keep logs clean.
        donate = () if ops_backend.on_cpu() else (1,)
        self._prefill_chunk = jax.jit(
            partial(prefill_chunk, self.model_cfg, mesh=self.mesh,
                    rules=rules),
            donate_argnums=donate)
        self._decode = jax.jit(
            partial(decode_step, self.model_cfg, mesh=self.mesh,
                    rules=rules),
            donate_argnums=donate)
        # Speculative decoding: jit the draft's prefill/decode and the
        # flagship's multi-token verify; the draft KV pool attaches to
        # the SAME block manager as an aux pool (one table, two pools).
        self._spec_armed = (self.config.spec_k > 0
                            and self.config.draft_model is not None)
        if self._spec_armed:
            if self.config.tp_size > 1:
                raise ValueError(
                    "speculative decoding is not supported with tp_size "
                    "> 1 (the draft aux pool is unsharded)")
            self.draft_cfg = self.config.draft_model
            if draft_params is None:
                draft_params = init_params(
                    self.draft_cfg,
                    jax.random.PRNGKey(self.config.param_seed + 1))
            self.draft_params = draft_params
            self.cache.attach_aux("draft", self.draft_cfg,
                                  dtype=self.config.cache_dtype)
            self._draft_prefill = jax.jit(
                partial(prefill_chunk, self.draft_cfg),
                donate_argnums=donate)
            self._draft_decode = jax.jit(
                partial(decode_step, self.draft_cfg),
                donate_argnums=donate)
            self._verify = jax.jit(
                partial(verify_step, self.model_cfg, mesh=self.mesh,
                        rules=rules),
                donate_argnums=donate)
        self._lock = threading.RLock()          # scheduler + cache + step
        self._work = threading.Event()          # submit -> loop wakeup
        self._stop = threading.Event()
        self._loop_thread: Optional[threading.Thread] = None
        self._requests: Dict[int, Request] = {}
        # Held-after-prefill sequences (disagg prefill pool): finished
        # requests whose KV blocks stay allocated for p2p export until
        # release_held() (decode-side ack) or the publish TTL fires.
        self._held: Dict[int, Request] = {}
        # -- counters --
        self.num_steps = 0
        self.num_prefill_tokens = 0      # prompt tokens actually computed
        self.num_generated_tokens = 0
        self.num_failed_requests = 0     # FAILED finishes (incl. the
        self.last_failure: Optional[str] = None  # loop's catch-all)
        # -- speculative-decoding counters --
        self.spec_rounds = 0             # verify steps run
        self.spec_proposed = 0           # draft tokens proposed
        self.spec_accepted = 0           # proposals the flagship accepted
        self.spec_emitted = 0            # tokens emitted by spec rounds
        self.spec_fallback_rounds = 0    # rounds vanilla-decoded instead
        # Per-request TTFT decomposition records (queue/prefill/decode/
        # ttft seconds), bounded: stats() serves percentile rollups —
        # the elastic episode's "where does TTFT live" evidence.
        from collections import deque as _deque

        self._timings: "_deque" = _deque(maxlen=2048)
        self.engine_id = next(_engine_ids)
        _ENGINES[self.engine_id] = self
        # Flight-recorder section: this engine's waitqueue depth, KV
        # occupancy, and TTFT decomposition render into every debug
        # bundle (weak-registered — a GC'd engine stops reporting via
        # the WeakValueDictionary, and stats() raising on a dead engine
        # is caught per-section at dump time).
        from ray_tpu._private import flight as _flight

        if _flight.active():
            eid = self.engine_id

            def _section(_id=eid):
                e = _ENGINES.get(_id)
                return e.stats() if e is not None else {"gone": True}

            _flight.add_section(f"llm.engine-{eid}", _section)

    # ------------------------------------------------------ tensor parallel
    @staticmethod
    def _build_tp_mesh(tp: int):
        """A tp-only mesh over the first ``tp`` devices (the standard
        framework axes, every other axis size 1, so the default
        ShardingRules apply unchanged — batch axes become no-op
        shards)."""
        import jax

        from ray_tpu.parallel.mesh import MeshConfig, make_mesh
        from ray_tpu.parallel.sharding import ShardingRules

        devices = jax.devices()
        if len(devices) < tp:
            raise ValueError(
                f"tp_size {tp} exceeds {len(devices)} visible devices")
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, pp=1, tp=tp, sp=1, ep=1),
                         devices=devices[:tp])
        return mesh, ShardingRules()

    def _param_specs(self, rules):
        cfg = self.model_cfg
        if cfg.n_heads % self.config.tp_size or \
                cfg.n_kv_heads % self.config.tp_size:
            raise ValueError(
                f"n_heads {cfg.n_heads} / n_kv_heads {cfg.n_kv_heads} "
                f"must divide tp_size {self.config.tp_size}")
        from ray_tpu.models import param_specs

        return param_specs(cfg, rules)

    # ------------------------------------------------------------ lifecycle
    def _ensure_loop(self):
        if self._loop_thread is None or not self._loop_thread.is_alive():
            self._loop_thread = threading.Thread(
                target=self._loop, daemon=True, name="llm-engine-step")
            self._loop_thread.start()

    def shutdown(self):
        self._stop.set()
        with self._lock:
            for req in list(self._requests.values()):
                if not req.finished():
                    # Remove from the waitqueue BEFORE finishing: a loop
                    # thread already past its stop-check blocks on this
                    # lock and would otherwise re-admit the CANCELLED
                    # request (reallocating blocks, streaming past DONE).
                    self.scheduler.remove_waiting(req)
                    self._finish(req, CANCELLED)
            for seq_id in list(self._held):
                self.release_held(seq_id)
        self._work.set()

    def _loop(self):
        while not self._stop.is_set():
            self._work.wait()
            if self._stop.is_set():
                return
            try:
                busy = self.step()
            except Exception as exc:  # noqa: BLE001 — engine must not die
                # An unexpected step failure (compile error, device OOM)
                # must not strand consumers on a dead loop thread: fail
                # every in-flight request TYPED (freeing its blocks) and
                # keep serving — the next submit sees a clean engine.
                with self._lock:
                    for req in list(self._requests.values()):
                        if not req.finished():
                            self.scheduler.remove_waiting(req)
                            self._finish(req, FAILED, exc)
                busy = True
                continue
            if not busy:
                idle = False
                with self._lock:
                    # Check + clear under the submit lock: a concurrent
                    # submit either lands before the check (not idle) or
                    # blocks until after the clear and re-sets the event.
                    if (not self.scheduler.running
                            and self.scheduler.queue_depth() == 0):
                        self._work.clear()
                        idle = True
                if not idle:
                    # Defensive: a non-admittable queue must not busy-spin.
                    time.sleep(0.001)

    # -------------------------------------------------------------- request
    def submit(self, prompt: List[int],
               max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0,
               seed: Optional[int] = None,
               priority: int = 0,
               trace=None,
               hold_after_prefill: bool = False) -> Request:
        """Enqueue a request. Past the bounded waitqueue the LOWEST
        priority class loses: either this submit raises
        ``EngineQueueFull`` (a ``RequestSheddedError``) or a worse
        already-waiting request is evicted with a typed
        ``RequestSheddedError`` on its stream — overload degrades by
        policy, not by timeout. Tokens arrive on ``req.output_queue``
        as iterations commit them."""
        req = Request(
            prompt,
            max_new_tokens if max_new_tokens is not None
            else self.config.max_new_tokens_default,
            eos_token_id=(eos_token_id if eos_token_id is not None
                          else self.config.eos_token_id),
            temperature=temperature, seed=seed, priority=priority)
        req.trace = trace
        req.hold_after_prefill = bool(hold_after_prefill)
        # Reject what can NEVER be served: a completion longer than the
        # model's context window, or one larger than the whole pool.
        # (Prompts over the prefill token budget are FINE — chunked
        # prefill spreads them across iterations.)
        total = len(req.prompt) + req.max_new_tokens
        max_len = getattr(self.model_cfg, "max_seq_len", None)
        if max_len is not None and total > max_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the model's "
                f"max_seq_len {max_len}")
        if self.cache.blocks_for_tokens(total) > self.cache.usable_blocks:
            raise KVCacheOOM(
                f"request needs {self.cache.blocks_for_tokens(total)} "
                f"blocks for {total} tokens; pool holds "
                f"{self.cache.usable_blocks}")
        with self._lock:
            victim = self.scheduler.submit(req)
            if victim is not None:
                # Evicted pre-admission (never held blocks): its consumer
                # gets the typed shed error, counted apart from failures.
                self._finish(victim, SHED, RequestSheddedError(
                    f"request (priority class {victim.priority}) evicted "
                    f"from the waitqueue by a class-{req.priority} "
                    f"arrival under overload",
                    priority=victim.priority))
            self._requests[req.seq_id] = req
            self._work.set()
        self._ensure_loop()
        return req

    def generate(self, prompt: List[int],
                 max_new_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0,
                 seed: Optional[int] = None,
                 priority: int = 0,
                 timeout_s: float = 120.0,
                 trace=None) -> Iterator[int]:
        """Streaming generator of token ids. Closing it mid-generation
        (``close()`` / GC / a Serve stream cancel) frees the sequence's
        private KV blocks immediately."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          eos_token_id=eos_token_id,
                          temperature=temperature, seed=seed,
                          priority=priority, trace=trace)
        try:
            while True:
                try:
                    item = req.output_queue.get(timeout=timeout_s)
                except queue.Empty:
                    raise TimeoutError(
                        f"no token for {timeout_s}s (sequence "
                        f"{req.seq_id}, status {req.status})") from None
                if isinstance(item, tuple):
                    kind, payload = item
                    if kind == _DONE:
                        return
                    raise payload  # _ERROR
                yield item
        finally:
            if not req.finished():
                self.cancel(req)

    def cancel(self, req) -> bool:
        """Cancel by Request or seq_id: removes it from the waitqueue or
        the running set and drops its block refs NOW."""
        with self._lock:
            if isinstance(req, int):
                req = self._requests.get(req)
            if req is None or req.finished():
                return False
            self.scheduler.remove_waiting(req)
            self._finish(req, CANCELLED)
        self._work.set()  # a parked admission may now fit
        return True

    def _finish(self, req: Request, status: str,
                error: Optional[BaseException] = None):
        self.scheduler.release(req, status, error)
        self._requests.pop(req.seq_id, None)
        req.t_finish = time.monotonic()
        self._record_timing(req, status)
        if status == FAILED:
            self.num_failed_requests += 1
            self.last_failure = repr(error)
        if status in (FAILED, SHED) and error is not None:
            req.output_queue.put((_ERROR, error))
        else:
            req.output_queue.put((_DONE, status))

    def _hold(self, req: Request):
        """Disagg prefill pool: retire a ``hold_after_prefill`` request
        WITHOUT freeing its KV blocks — they stay allocated (and
        prefix-registered) for p2p export until ``release_held`` (the
        decode side's ack) or the publish TTL sweeps them. Consumer-
        visible stream behavior is identical to ``_finish``."""
        self.scheduler.release(req, FINISHED, free_blocks=False)
        self._requests.pop(req.seq_id, None)
        self._held[req.seq_id] = req
        req.t_finish = time.monotonic()
        self._record_timing(req, FINISHED)
        req.output_queue.put((_DONE, FINISHED))

    def release_held(self, seq_id: int) -> int:
        """Free a held sequence's blocks (decode-side ack, TTL expiry,
        or shutdown). Idempotent — ack and the TTL sweep may race; the
        loser sees 0. Returns blocks actually freed."""
        with self._lock:
            if self._held.pop(seq_id, None) is None:
                return 0
            freed = self.cache.free(seq_id)
        self._work.set()  # a parked admission may now fit
        return freed

    def held_count(self) -> int:
        with self._lock:
            return len(self._held)

    # ------------------------------------------------------ disagg adoption
    def begin_adopted(self, prompt: List[int],
                      max_new_tokens: Optional[int] = None,
                      eos_token_id: Optional[int] = None,
                      temperature: float = 0.0,
                      seed: Optional[int] = None,
                      priority: int = 0,
                      trace=None) -> Optional[Request]:
        """Disagg decode pool, step 1 of 3: allocate the prompt's block
        table as admission would (sharing every prefix-cached leading
        block) so a prefill replica's exported KV can be grafted into
        it. Returns None when the batch or pool has no room RIGHT NOW —
        adoption is an optimization, never a queueing state; the caller
        falls back to the colocated path. The returned request is
        cancellable and shutdown-safe like any other, but runs only
        after ``commit_adopted``."""
        req = Request(
            prompt,
            max_new_tokens if max_new_tokens is not None
            else self.config.max_new_tokens_default,
            eos_token_id=(eos_token_id if eos_token_id is not None
                          else self.config.eos_token_id),
            temperature=temperature, seed=seed, priority=priority)
        req.trace = trace
        total = len(req.prompt) + req.max_new_tokens
        max_len = getattr(self.model_cfg, "max_seq_len", None)
        if max_len is not None and total > max_len:
            return None
        with self._lock:
            if len(self.scheduler.running) >= self.config.max_num_seqs:
                return None
            cached = self.cache.allocate_prefix(
                req.seq_id, req.prompt, extra_tokens=1)
            if cached is None:
                return None
            req.cached_prompt_tokens = cached
            req.t_sched = time.monotonic()
            self._requests[req.seq_id] = req
        return req

    def abort_adopted(self, req: Request) -> None:
        """Undo ``begin_adopted`` (the remote prefill or the p2p pull
        failed): drop the allocation and forget the request. The caller
        retries on the colocated path with a FRESH submit."""
        with self._lock:
            self._requests.pop(req.seq_id, None)
            self.cache.free(req.seq_id)
        self._work.set()

    def adopt_kv(self, req: Request, payload: dict) -> bool:
        """Disagg step 2: graft the prefill replica's exported blocks
        into this pool under the adopted sequence's table. Blocks
        before the locally prefix-cached boundary are NEVER written
        (they are shared with their other holders); the payload must
        cover everything from that boundary on or the graft is refused
        (False — the shipping plan went stale, caller falls back). On
        success the full prompt registers in the prefix cache and the
        transfer phase stamp closes."""
        graft_from = req.cached_prompt_tokens // self.cache.block_size
        if (int(payload.get("block_size", -1)) != self.cache.block_size
                or int(payload.get("start_block", 0)) > graft_from):
            return False
        with self._lock:
            try:
                self.cache.graft_blocks(req.seq_id, payload,
                                        start_block=graft_from)
            except (KeyError, ValueError):
                return False
            self.cache.register_prefix(req.seq_id, len(req.prompt))
        nbytes = 0
        for part in (payload, *payload.get("aux", {}).values()):
            for name in ("k", "v"):
                arr = part.get(name)
                if arr is not None:
                    nbytes += int(getattr(arr, "nbytes", 0))
        req.kv_ship = (int(payload.get("blocks", 0)), nbytes)
        now = time.monotonic()
        if req.t_prefill_done is None:
            # The caller normally stamps this when the remote prefill
            # RPC returns; backfill keeps transfer_s >= 0 regardless.
            req.t_prefill_done = now
        req.t_transfer_done = now
        return True

    def commit_adopted(self, req: Request, first_token: int) -> None:
        """Disagg step 3: the grafted sequence becomes a live decode
        row. Streams the prefill replica's first token (sampled there
        from the final chunk's logits — identical to the colocated
        path) and joins the running set at the decode phase; EOS or a
        1-token budget finishes immediately."""
        tok = int(first_token)
        with self._lock:
            now = time.monotonic()
            if req.t_prefill_done is None:
                req.t_prefill_done = now
            if req.t_transfer_done is None:
                req.t_transfer_done = now
            req.prefill_pos = len(req.prompt)
            req.t_first_token = now
            req.out_tokens.append(tok)
            self.num_generated_tokens += 1
            req.output_queue.put(tok)
            if ((req.eos_token_id is not None
                    and tok == req.eos_token_id)
                    or len(req.out_tokens) >= req.max_new_tokens):
                self._finish(req, FINISHED)
                return
            self.scheduler.adopt_running(req)
            self._work.set()
        self._ensure_loop()

    def _record_timing(self, req: Request, status: str):
        """TTFT decomposition record + (when the request carried a trace
        context) llm.queue / llm.prefill / llm.decode spans with a
        first_token event — the per-request waterfall's engine rows."""
        t_end = req.t_finish
        queue_s = ((req.t_sched - req.t_submit)
                   if req.t_sched is not None else t_end - req.t_submit)
        prefill_s = ((req.t_prefill_done - req.t_sched)
                     if req.t_sched is not None
                     and req.t_prefill_done is not None else 0.0)
        # Disagg-adopted sequences add a TRANSFER phase (p2p KV pull +
        # graft) between prefill and decode; colocated requests have
        # none and their decode starts at t_prefill_done.
        transfer_s = ((req.t_transfer_done - req.t_prefill_done)
                      if req.t_transfer_done is not None
                      and req.t_prefill_done is not None else 0.0)
        t_decode0 = (req.t_transfer_done
                     if req.t_transfer_done is not None
                     else req.t_prefill_done)
        decode_s = (t_end - t_decode0) if t_decode0 is not None else 0.0
        self._timings.append({
            "status": status,
            "queue_s": queue_s,
            "prefill_s": prefill_s,
            "transfer_s": transfer_s,
            "decode_s": decode_s,
            "ttft_s": ((req.t_first_token - req.t_submit)
                       if req.t_first_token is not None else None),
            "total_s": t_end - req.t_submit,
        })
        from ray_tpu._private import tracing

        t = tracing.tracer()
        if t is None or req.trace is None:
            return
        ctx = tracing.extract(req.trace)
        if ctx is None:
            return
        # Monotonic stamps anchor to the submit wall clock for spans.
        def wall(mono):
            return req.wall_submit + (mono - req.t_submit)

        ok = "ok" if status == FINISHED else "error"
        if req.t_sched is not None:
            t.emit(ctx.trace_id, tracing._new_id(), ctx.span_id,
                   "llm.queue", wall(req.t_submit), queue_s,
                   component="llm", tags={"seq": req.seq_id})
            if req.t_prefill_done is not None:
                t.emit(ctx.trace_id, tracing._new_id(), ctx.span_id,
                       "llm.prefill", wall(req.t_sched), prefill_s,
                       component="llm",
                       tags={"seq": req.seq_id,
                             "cached_tokens": req.cached_prompt_tokens})
                if req.t_transfer_done is not None:
                    blocks, nbytes = req.kv_ship or (0, 0)
                    t.emit(ctx.trace_id, tracing._new_id(), ctx.span_id,
                           "llm.kv_ship", wall(req.t_prefill_done),
                           transfer_s, component="llm",
                           tags={"seq": req.seq_id, "blocks": blocks,
                                 "bytes": nbytes})
                events = []
                if req.t_first_token is not None:
                    events.append([wall(req.t_first_token),
                                   "first_token"])
                t.emit(ctx.trace_id, tracing._new_id(), ctx.span_id,
                       "llm.decode", wall(t_decode0), decode_s,
                       status=ok, component="llm",
                       tags={"seq": req.seq_id,
                             "tokens": len(req.out_tokens)},
                       events=events)
        else:
            # Never scheduled (shed/cancelled in the waitqueue).
            t.emit(ctx.trace_id, tracing._new_id(), ctx.span_id,
                   "llm." + status.lower(), wall(req.t_submit), queue_s,
                   status=ok, component="llm",
                   tags={"seq": req.seq_id})

    # ----------------------------------------------------------------- step
    def step(self) -> bool:
        """Run ONE continuous-batching iteration: admit + one prefill
        chunk per prefilling sequence (under the token budget) + one
        decode for every fully-prefilled sequence. Returns True if any
        work ran. Public so tests/bench can drive deterministically."""
        with self._lock:
            try:
                chunks, decodes = self.scheduler.schedule()
            except MemoryError as e:
                # A single sequence outgrew the pool: fail it, keep going.
                for r in list(self.scheduler.running):
                    self._finish(r, FAILED, KVCacheOOM(str(e)))
                return True
            if not chunks and not decodes:
                # Parked head with nothing running: no future free() can
                # unpark it (submit-time checks bound single requests, but
                # fragmentation from a dead pool must not spin forever).
                if (self.scheduler.queue_depth() > 0
                        and not self.scheduler.running
                        and not self.cache.can_allocate(1)):
                    head = self.scheduler.waiting[0]
                    self.scheduler.remove_waiting(head)
                    self._finish(head, FAILED, KVCacheOOM(
                        "KV pool exhausted with no running sequences to "
                        "free blocks"))
                return False
            if chunks:
                self._run_prefill_chunks(chunks)
            # Newly completed prefills join decode NEXT iteration; their
            # first token came out of the chunk logits.
            if decodes:
                decodes = [r for r in decodes if not r.finished()]
            if decodes:
                if self._spec_armed:
                    self._run_spec_decode(decodes)
                else:
                    self._run_decode(decodes)
            self.num_steps += 1
            return True

    def _run_prefill_chunks(self, chunks: List[Tuple[Request, int, int]]):
        import jax.numpy as jnp

        bs = self.cache.block_size
        b_pad = _pow2_at_least(len(chunks))
        max_chunk = max(n for _, _, n in chunks)
        c_pad = _pow2_at_least(max_chunk)
        tokens = np.zeros((b_pad, c_pad), np.int32)
        starts = np.zeros((b_pad,), np.int32)
        lens = np.ones((b_pad,), np.int32)
        for i, (r, start, n) in enumerate(chunks):
            tokens[i, :n] = r.prompt[start:start + n]
            starts[i] = start
            lens[i] = n
        tables = self.cache.padded_tables([r.seq_id for r, _, _ in chunks])
        # Cover every position this program may touch, including padded
        # chunk tails (their writes must resolve to real table entries
        # or the NULL padding, never clamp onto a live block).
        need_m = max((int(s) + c_pad - 1) // bs + 1
                     for s in starts[:len(chunks)])
        m_pad = _pow2_at_least(max(tables.shape[1], need_m))
        bt = np.zeros((b_pad, m_pad), np.int32)
        bt[:len(chunks), :tables.shape[1]] = tables
        logits, self.cache.data = self._prefill_chunk(
            self.params, self.cache.data, jnp.asarray(tokens),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(bt))
        if self._spec_armed:
            # The draft's KV rides the SAME chunk plan into its aux
            # pool — after prefill both models hold the prompt's cache
            # and the first spec round can draft immediately.
            _, draft_data = self._draft_prefill(
                self.draft_params, self.cache.aux_data("draft"),
                jnp.asarray(tokens), jnp.asarray(starts),
                jnp.asarray(lens), jnp.asarray(bt))
            self.cache.set_aux_data("draft", draft_data)
        logits = None if not any(
            start + n >= len(r.prompt) for r, start, n in chunks) \
            else np.asarray(logits)
        completed: List[Request] = []
        rows: List[int] = []
        for i, (r, start, n) in enumerate(chunks):
            self.num_prefill_tokens += n
            r.prefill_pos = start + n
            # Blocks computed so far become shareable immediately — a
            # concurrent same-prefix request hits them mid-prefill.
            self.cache.register_prefix(r.seq_id, r.prefill_pos)
            if r.prefill_pos >= len(r.prompt):
                r.t_prefill_done = time.monotonic()
                completed.append(r)
                rows.append(i)
        if completed:
            self._emit(completed, logits[rows])

    def _run_decode(self, reqs: List[Request]):
        import jax.numpy as jnp

        bs = self.cache.block_size
        b_pad = _pow2_at_least(len(reqs))
        tokens = np.zeros((b_pad,), np.int32)
        positions = np.zeros((b_pad,), np.int32)
        for i, r in enumerate(reqs):
            tokens[i] = r.last_token
            positions[i] = r.num_tokens - 1  # slot this step writes
        tables = self.cache.padded_tables([r.seq_id for r in reqs])
        m_pad = max(_pow2_at_least(tables.shape[1]),
                    (int(positions.max()) // bs) + 1)
        bt = np.zeros((b_pad, m_pad), np.int32)
        bt[:len(reqs), :tables.shape[1]] = tables
        logits, self.cache.data = self._decode(
            self.params, self.cache.data, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(bt))
        self._emit(reqs, np.asarray(logits)[:len(reqs)])

    def _run_spec_decode(self, reqs: List[Request]):
        """One SPECULATIVE round: the draft proposes ``spec_k`` greedy
        tokens per sequence (its KV riding the shared block tables in
        the aux pool), the flagship scores ``[last_token, d_1..d_k]``
        in ONE ``verify_step``, and the longest agreeing prefix plus
        one bonus token from the verify logits commits — 1 to k+1
        tokens per sequence per iteration, token-for-token identical
        to vanilla greedy decode (the flagship's argmax is always the
        authority; the draft only picks how many positions one step
        scores).

        Fallback to a vanilla round (counted) when any row samples at
        temperature > 0 (spec is greedy-only) or the k lookahead slots
        don't all allocate. Stale lookahead KV past an accepted prefix
        is masked by context length until the NEXT round's writes —
        which always cover it — land (see ``verify_step``)."""
        k = self.config.spec_k
        if any(r.temperature > 0.0 for r in reqs):
            self.spec_fallback_rounds += 1
            return self._run_decode(reqs)
        # schedule() guaranteed position num_tokens-1 (+1 headroom);
        # verify also writes num_tokens .. num_tokens+k-1.
        for r in reqs:
            for pos in range(r.num_tokens, r.num_tokens + k):
                if not self.cache.ensure_slot(r.seq_id, pos):
                    self.spec_fallback_rounds += 1
                    return self._run_decode(reqs)
        import jax.numpy as jnp

        bs = self.cache.block_size
        b = len(reqs)
        b_pad = _pow2_at_least(b)
        c_pad = _pow2_at_least(k + 1)
        tables = self.cache.padded_tables([r.seq_id for r in reqs])
        # Cover every position verify's padded columns may touch —
        # block lookups CLAMP to the last table column, so positions
        # past a row's real table must resolve to the zero (NULL) pad,
        # never onto its last live block.
        need_m = max((r.num_tokens - 1 + c_pad - 1) // bs + 1
                     for r in reqs)
        m_pad = _pow2_at_least(max(tables.shape[1], need_m))
        bt = np.zeros((b_pad, m_pad), np.int32)
        bt[:b, :tables.shape[1]] = tables
        bt_j = jnp.asarray(bt)

        # Draft pass: k sequential one-token steps over the aux pool.
        draft_data = self.cache.aux_data("draft")
        proposals = np.zeros((b, k), np.int32)
        cur = np.zeros((b_pad,), np.int32)
        pos = np.zeros((b_pad,), np.int32)
        for i, r in enumerate(reqs):
            cur[i] = r.last_token
        for j in range(k):
            for i, r in enumerate(reqs):
                pos[i] = r.num_tokens - 1 + j
            logits, draft_data = self._draft_decode(
                self.draft_params, draft_data, jnp.asarray(cur),
                jnp.asarray(pos), bt_j)
            nxt = np.argmax(np.asarray(logits)[:b], axis=-1)
            proposals[:, j] = nxt
            cur[:b] = nxt
        self.cache.set_aux_data("draft", draft_data)

        # Verify pass: one flagship step scores all k proposals.
        vtok = np.zeros((b_pad, c_pad), np.int32)
        starts = np.zeros((b_pad,), np.int32)
        for i, r in enumerate(reqs):
            vtok[i, 0] = r.last_token
            vtok[i, 1:k + 1] = proposals[i]
            starts[i] = r.num_tokens - 1
        logits, self.cache.data = self._verify(
            self.params, self.cache.data, jnp.asarray(vtok),
            jnp.asarray(starts), bt_j)
        logits = np.asarray(logits)[:b, :k + 1]

        self.spec_rounds += 1
        self.spec_proposed += b * k
        for i, req in enumerate(reqs):
            row = logits[i]
            accepted = 0
            while accepted < k and int(np.argmax(row[accepted])) \
                    == int(proposals[i, accepted]):
                accepted += 1
            self.spec_accepted += accepted
            # Accepted proposals + one bonus token (the flagship's own
            # next token after the accepted prefix) — exactly what
            # sequential greedy decode would have produced.
            toks = [int(proposals[i, j]) for j in range(accepted)]
            toks.append(int(np.argmax(row[accepted])))
            if req.t_first_token is None:
                req.t_first_token = time.monotonic()
            for tok in toks:
                req.out_tokens.append(tok)
                self.num_generated_tokens += 1
                self.spec_emitted += 1
                req.output_queue.put(tok)
                if ((req.eos_token_id is not None
                        and tok == req.eos_token_id)
                        or len(req.out_tokens) >= req.max_new_tokens):
                    if req.hold_after_prefill:
                        self._hold(req)
                    else:
                        self._finish(req, FINISHED)
                    break

    def _emit(self, reqs: List[Request], logits: np.ndarray):
        """Sample one token per request from its logits row, stream it,
        and retire sequences that hit EOS / their token budget."""
        for i, req in enumerate(reqs):
            tok = self._sample(req, logits[i])
            if req.t_first_token is None:
                req.t_first_token = time.monotonic()
            req.out_tokens.append(tok)
            self.num_generated_tokens += 1
            req.output_queue.put(tok)
            if ((req.eos_token_id is not None and tok == req.eos_token_id)
                    or len(req.out_tokens) >= req.max_new_tokens):
                if req.hold_after_prefill:
                    self._hold(req)
                else:
                    self._finish(req, FINISHED)

    @staticmethod
    def _sample(req: Request, row: np.ndarray) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(row))
        # Per-request deterministic sampling stream (seeded, host-side).
        rng = np.random.default_rng(
            (req.seed if req.seed is not None else req.seq_id,
             len(req.out_tokens)))
        z = row.astype(np.float64) / req.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(len(row), p=p))

    # -------------------------------------------------------------- queries
    def queue_depth(self) -> int:
        return self.scheduler.queue_depth()

    def stats(self) -> Dict[str, Any]:
        out = {
            "engine_id": self.engine_id,
            "tp_size": self.config.tp_size,
            "steps": self.num_steps,
            "prefill_tokens": self.num_prefill_tokens,
            "generated_tokens": self.num_generated_tokens,
            "failed_requests": self.num_failed_requests,
            "last_failure": self.last_failure,
            "ttft_decomposition": self.ttft_decomposition(),
            "held_sequences": len(self._held),
        }
        if self._spec_armed:
            out["spec"] = {
                "k": self.config.spec_k,
                "rounds": self.spec_rounds,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "emitted": self.spec_emitted,
                "fallback_rounds": self.spec_fallback_rounds,
                "acceptance_rate": (self.spec_accepted
                                    / max(1, self.spec_proposed)),
            }
        out.update(self.scheduler.stats())
        out.update(self.cache.stats())
        # The process that holds the model says where it runs, so a
        # caller elsewhere can refuse a CPU result.
        from ray_tpu.ops.backend import device_info

        out.update(device_info())
        return out

    def ttft_decomposition(self) -> Dict[str, Any]:
        """Percentile rollup of the per-request timing records: where
        TTFT lives (queue wait vs prefill vs decode) on this engine."""
        rows = [r for r in list(self._timings)
                if r["status"] == FINISHED]
        if not rows:
            return {"completed": 0}

        def pct(key, q):
            vals = sorted(r[key] for r in rows
                          if r.get(key) is not None)
            if not vals:
                return None
            return vals[min(len(vals) - 1, int(len(vals) * q))]

        return {
            "completed": len(rows),
            "queue_p50_s": pct("queue_s", 0.5),
            "queue_p99_s": pct("queue_s", 0.99),
            "prefill_p50_s": pct("prefill_s", 0.5),
            "prefill_p99_s": pct("prefill_s", 0.99),
            "transfer_p50_s": pct("transfer_s", 0.5),
            "transfer_p99_s": pct("transfer_s", 0.99),
            "decode_p50_s": pct("decode_s", 0.5),
            "decode_p99_s": pct("decode_s", 0.99),
            "ttft_p50_s": pct("ttft_s", 0.5),
            "ttft_p99_s": pct("ttft_s", 0.99),
        }

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        """Block until no work remains (tests/bench convenience)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if (not self.scheduler.running
                        and self.scheduler.queue_depth() == 0):
                    return True
            time.sleep(0.002)
        return False
