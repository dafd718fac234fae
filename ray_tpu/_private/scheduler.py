"""Local task scheduler: dependency resolution, resource-aware dispatch,
retries, lineage.

Single-node rebuild of the reference's scheduling stack — the roles of
NormalTaskSubmitter (owner-side submit), DependencyManager (wait for arg
objects), LocalTaskManager (acquire resources + dispatch to a worker), and
TaskManager (retries + lineage) (reference: src/ray/core_worker/transport/,
src/ray/raylet/ [unverified]). The multi-node path reuses this per node
behind the control plane in ray_tpu/_private/node.py; the compiled-graph
path in ray_tpu/dag bypasses it entirely (SURVEY.md §2.3 north star).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.log import get_logger
from ray_tpu._private.task_events import TaskEventBuffer
from ray_tpu._private import tracing

log = get_logger(__name__)
from ray_tpu.exceptions import (
    RayTaskError,
    RuntimeEnvSetupError,
    TaskCancelledError,
)


@dataclass
class TaskSpec:
    """Immutable description of a submitted task (TaskSpecification parity)."""

    task_id: TaskID
    function: Callable
    args: Tuple[Any, ...]
    kwargs: Dict[str, Any]
    num_returns: int
    return_ids: List[ObjectID]
    name: str = ""
    resources: Dict[str, float] = field(default_factory=dict)
    max_retries: int = 0
    retry_exceptions: bool = False
    scheduling_strategy: Any = None
    runtime_env: Any = None
    # Streaming generator (num_returns="streaming"): return_ids holds only
    # the END MARKER; item objects commit dynamically per yield, with the
    # producer pausing at `backpressure` committed-but-unconsumed items.
    streaming: bool = False
    backpressure: int = 0
    # Trace context wire form ((trace_id, span_id) or None): captured
    # from the submitting thread's ambient context when tracing is
    # armed; rides task payloads across the wire (tracing.py).
    trace: Any = None
    # Filled by the scheduler:
    attempt: int = 0


class ResourcePool:
    """Node-local resource bookkeeping (CPU/TPU/custom, fractional allowed)."""

    def __init__(self, total: Dict[str, float]):
        self._total = dict(total)
        self._available = dict(total)
        self._cv = threading.Condition()
        self._release_listeners: List[Callable[[], None]] = []

    def add_release_listener(self, cb: Callable[[], None]):
        """Event-driven wakeup hook: ``cb`` fires after every release,
        OUTSIDE the pool lock (listeners may take their own locks that
        also nest around try_acquire — calling under the pool lock
        would close an ABBA cycle)."""
        with self._cv:
            self._release_listeners.append(cb)

    @property
    def total(self) -> Dict[str, float]:
        return dict(self._total)

    def available(self) -> Dict[str, float]:
        with self._cv:
            return dict(self._available)

    def fits(self, demand: Dict[str, float]) -> bool:
        return all(self._total.get(k, 0.0) >= v for k, v in demand.items())

    def try_acquire(self, demand: Dict[str, float]) -> bool:
        with self._cv:
            if all(self._available.get(k, 0.0) >= v - 1e-9
                   for k, v in demand.items()):
                for k, v in demand.items():
                    self._available[k] = self._available.get(k, 0.0) - v
                return True
            return False

    def release(self, demand: Dict[str, float]):
        with self._cv:
            for k, v in demand.items():
                self._available[k] = self._available.get(k, 0.0) + v
            self._cv.notify_all()
            listeners = list(self._release_listeners)
        for cb in listeners:
            cb()

    def wait_for_change(self, timeout: float = 0.5):
        with self._cv:
            self._cv.wait(timeout)

    def utilization(self) -> float:
        with self._cv:
            fracs = [
                1.0 - self._available.get(k, 0.0) / v
                for k, v in self._total.items() if v > 0
            ]
            return max(fracs) if fracs else 0.0


class LocalScheduler:
    """Dependency-resolving, resource-aware FIFO dispatcher over a worker
    thread pool, with retry + cancellation support."""

    def __init__(self, store, resource_pool: ResourcePool, num_workers: int,
                 task_events: Optional[TaskEventBuffer] = None,
                 lineage: Optional[dict] = None,
                 worker_pool=None, shm_store=None,
                 use_native_queue: Optional[bool] = None):
        self._store = store
        self._resources = resource_pool
        self._pool = ThreadPoolExecutor(
            max_workers=num_workers, thread_name_prefix="ray_tpu_worker"
        )
        self._events = task_events
        self._lineage = lineage if lineage is not None else {}
        self._lock = threading.Lock()
        # Runnable tasks bucketed by resource shape: dispatch picks the
        # lowest-sequence head whose shape fits *now*, trying each
        # distinct shape at most once per drain — O(#shapes) per
        # dispatched task instead of the old O(len(runnable)) FIFO scan
        # that re-tried every queued task's acquire on every wakeup.
        self._runnable: Dict[tuple, Any] = {}  # shape -> deque[(seq, spec)]
        self._runnable_count = 0
        self._runnable_seq = 0
        self._pending_deps: Dict[TaskID, int] = {}
        self._cancelled: set = set()
        self._running: Dict[TaskID, threading.Event] = {}
        self._shutdown = False
        self._backlog = 0
        self._num_finished = 0
        self._dispatch_cv = threading.Condition(self._lock)
        # Process execution plane (WorkerPool + shm object store); tasks run
        # in worker processes when present, in the thread pool otherwise.
        self._worker_pool = worker_pool
        self._shm_store = shm_store
        self._proc_running: Dict[TaskID, Any] = {}  # task -> WorkerProcess
        # Plasma-parity data path: successful task outputs STAY in the shm
        # store; a consumer task's ref args pass as shm keys the worker
        # reads directly, so values don't round-trip through the driver.
        # Entries release when the python store evicts the object.
        # IMPORTANT: accessed with GIL-atomic dict ops ONLY, never under
        # self._lock — the evict callback fires while the store holds ITS
        # lock, and taking the scheduler lock there would close an ABBA
        # cycle with _submit_native (scheduler lock -> store.contains).
        self._shm_resident: Dict[Any, int] = {}  # ObjectID -> shm key
        self._shm_key_pins: Dict[int, int] = {}  # key -> in-flight count
        self._deferred_deletes: set = set()  # pinned keys awaiting delete
        self._pin_lock = threading.Lock()  # leaf lock: nothing nests in it
        # Unpin events wake _clear_ret_keys waiters (no sleep-poll).
        self._pin_cv = threading.Condition(self._pin_lock)
        # Tasks whose workers the memory monitor killed: their crash is
        # reported as OutOfMemoryError, not a generic worker crash.
        self._oom_killed: set = set()
        if shm_store is not None:
            store.set_evict_callback(self._release_shm_resident)
        # Native dependency queue: the C++ ready-ring replaces the python
        # callback chain for deps between normal tasks.
        self._dq = None
        self._dq_handles: Dict[TaskID, int] = {}   # pending task -> handle
        self._dq_specs: Dict[int, TaskSpec] = {}
        if use_native_queue is None:
            use_native_queue = GlobalConfig.use_native_queue
        if use_native_queue:
            try:
                from ray_tpu._native.store import NativeDynQueue

                self._dq = NativeDynQueue()
            except Exception:  # noqa: BLE001 — native layer optional
                self._dq = None
        if self._dq is not None:
            self._dq_pump = threading.Thread(
                target=self._dq_pump_loop, daemon=True,
                name="ray_tpu_dq_pump",
            )
            self._dq_pump.start()
        # Event-driven dispatch: resource release signals the dispatch
        # condition instead of the loop polling wait_for_change(0.05).
        resource_pool.add_release_listener(self._on_resources_released)
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="ray_tpu_dispatcher",
        )
        self._dispatcher.start()

    # ------------------------------------------------------------ submission
    def submit(self, spec: TaskSpec):
        """Owner-side submit: record lineage, wait for deps, then queue."""
        if self._events:
            self._events.record(spec.task_id, "PENDING_ARGS_AVAIL",
                                name=spec.name)
        self._lineage[spec.return_ids[0].task_id()] = spec
        dep_refs = _collect_refs(spec.args, spec.kwargs)
        if not dep_refs:
            # Born-ready fast path: queue for dispatch directly. Routing
            # through the native ring (alloc + commit + a pump-thread
            # hop) buys nothing for a task with no pending producers.
            with self._lock:
                self._backlog += 1
                self._make_runnable_locked(spec)
            return
        if self._dq is not None:
            try:
                return self._submit_native(spec, dep_refs)
            except MemoryError:
                pass  # queue full: fall through to the python path
        with self._lock:
            self._backlog += 1
            self._pending_deps[spec.task_id] = len(dep_refs)

        def _on_dep_ready():
            with self._lock:
                remaining = self._pending_deps.get(spec.task_id)
                if remaining is None:
                    return
                remaining -= 1
                if remaining == 0:
                    del self._pending_deps[spec.task_id]
                    self._make_runnable_locked(spec)
                else:
                    self._pending_deps[spec.task_id] = remaining

        for ref in dep_refs:
            self._store.on_ready(ref.object_id, _on_dep_ready)

    def _submit_native(self, spec: TaskSpec, dep_refs: list):
        """Dependency tracking through the C++ ready-ring: deps between
        pending normal tasks become native edges; anything else (puts,
        actor outputs, recovering objects) gates the commit via the store
        callback."""
        dq = self._dq
        handle = dq.alloc()  # MemoryError -> caller falls back
        fallback_refs = []
        try:
            with self._lock:
                self._backlog += 1
                self._dq_handles[spec.task_id] = handle
                self._dq_specs[handle] = spec
                registered = False
                try:
                    for ref in dep_refs:
                        producer = self._dq_handles.get(
                            ref.object_id.task_id())
                        if self._store.contains(ref.object_id):
                            continue
                        if producer is not None and producer != handle:
                            dq.add_dep(handle, producer)
                        else:
                            fallback_refs.append(ref)
                    if not fallback_refs:
                        dq.commit(handle)
                        registered = True
                        return
                    self._pending_deps[spec.task_id] = len(fallback_refs)
                    registered = True
                finally:
                    if not registered:
                        # ANY failure mid-registration (edge table full,
                        # a raising store/commit, bad ids) unwinds
                        # everything this call registered so the
                        # caller's python-path fallback starts from a
                        # clean slate (no double-counted backlog, no
                        # stale never-completed handle for consumers to
                        # dep on). MemoryError-only unwind used to leak
                        # _backlog — and the handle — on every other
                        # exception class.
                        del self._dq_handles[spec.task_id]
                        del self._dq_specs[handle]
                        self._backlog -= 1
        except Exception:
            dq.abort(handle)  # recycle the slot; edges into it go stale
            raise

        def _on_dep_ready():
            with self._lock:
                remaining = self._pending_deps.get(spec.task_id)
                if remaining is None:
                    return
                remaining -= 1
                if remaining == 0:
                    del self._pending_deps[spec.task_id]
                else:
                    self._pending_deps[spec.task_id] = remaining
                    return
            dq.commit(handle)

        for ref in fallback_refs:
            self._store.on_ready(ref.object_id, _on_dep_ready)

    def _dq_pump_loop(self):
        """Drain the native ready-ring into the dispatch queue."""
        while True:
            with self._lock:
                if self._shutdown:
                    return
            handles = self._dq.pop(1024, timeout_s=0.2)
            if not handles:
                continue
            with self._lock:
                for h in handles:
                    spec = self._dq_specs.pop(h, None)
                    if spec is not None:
                        self._make_runnable_locked(spec)

    def _finalize_native(self, spec: TaskSpec):
        """Outputs are final: release the native slot, readying consumers."""
        if self._dq is None:
            return
        with self._lock:
            handle = self._dq_handles.pop(spec.task_id, None)
            self._dq_specs.pop(handle, None)
        if handle is not None:
            try:
                self._dq.complete(handle)
            except ValueError:
                pass

    def _make_runnable_locked(self, spec: TaskSpec):
        self._runnable_seq += 1
        dq = self._runnable.get(_shape_key(spec.resources))
        if dq is None:
            dq = self._runnable[_shape_key(spec.resources)] = deque()
        dq.append((self._runnable_seq, spec))
        self._runnable_count += 1
        if self._events:
            self._events.record(spec.task_id, "PENDING_NODE_ASSIGNMENT",
                                name=spec.name)
        self._dispatch_cv.notify_all()

    def queued_specs(self) -> List[TaskSpec]:
        """Snapshot of runnable-but-undispatched tasks in FIFO order."""
        with self._lock:
            items = [item for dq in self._runnable.values() for item in dq]
        items.sort(key=lambda it: it[0])
        return [spec for _, spec in items]

    # -------------------------------------------------------------- dispatch
    def _drain_dispatchable_locked(self, limit: int = 0) -> List[TaskSpec]:
        """Pop every runnable task whose resources fit right now (up to
        ``limit`` when nonzero), FIFO across shape buckets. A shape that
        fails try_acquire is skipped for the rest of the drain — its
        whole bucket cannot fit until something releases."""
        batch: List[TaskSpec] = []
        blocked: Optional[set] = None
        while self._runnable_count:
            best_key = None
            best_seq = 0
            for key, dq in self._runnable.items():
                if blocked is not None and key in blocked:
                    continue
                seq = dq[0][0]
                if best_key is None or seq < best_seq:
                    best_key, best_seq = key, seq
            if best_key is None:
                break
            dq = self._runnable[best_key]
            spec = dq[0][1]
            if self._resources.try_acquire(spec.resources):
                dq.popleft()
                if not dq:
                    del self._runnable[best_key]
                self._runnable_count -= 1
                batch.append(spec)
                if limit and len(batch) >= limit:
                    break
            else:
                if blocked is None:
                    blocked = set()
                blocked.add(best_key)
        return batch

    def _on_resources_released(self):
        """ResourcePool release listener (called outside the pool lock):
        wake dispatch if anything is waiting on capacity."""
        with self._lock:
            if self._runnable_count and not self._shutdown:
                self._dispatch_cv.notify_all()

    def _dispatch_loop(self):
        while True:
            with self._lock:
                while True:
                    if self._shutdown:
                        return
                    batch = self._drain_dispatchable_locked()
                    if batch:
                        break
                    # Event-driven: woken by _make_runnable_locked, the
                    # resource-release listener, or shutdown. No timed
                    # poll remains on this edge.
                    self._dispatch_cv.wait()
            for spec in batch:
                self._pool.submit(self._execute, spec)

    # ------------------------------------------------------------- execution
    def _pick_next_inline(self) -> Optional[TaskSpec]:
        """Work-continuation: the worker thread that just finished a task
        pulls the next fitting one itself, skipping the release→notify→
        dispatch→pool round trip (two context switches per task on the
        hot path)."""
        with self._lock:
            if self._shutdown:
                return None
            batch = self._drain_dispatchable_locked(limit=1)
        return batch[0] if batch else None

    def _execute(self, spec: TaskSpec):
        nxt: Optional[TaskSpec] = spec
        while nxt is not None:
            nxt = self._execute_one(nxt)

    def _execute_one(self, spec: TaskSpec) -> Optional[TaskSpec]:
        from ray_tpu._private import worker as worker_mod

        cancelled_event = threading.Event()
        with self._lock:
            cancelled_now = spec.task_id in self._cancelled
            if not cancelled_now:
                self._running[spec.task_id] = cancelled_event
        if cancelled_now:
            # OUTSIDE the lock: _finish_cancelled -> _finalize_native
            # re-acquires it (self-deadlock on the non-reentrant lock
            # otherwise — the teardown hang when cancel races dispatch).
            self._resources.release(spec.resources)
            self._finish_cancelled(spec)
            return self._pick_next_inline()

        if self._events:
            self._events.record(spec.task_id, "RUNNING", name=spec.name)
        start = time.monotonic()
        retry_spec = None
        try:
            if self._worker_pool is not None:
                pinned: list = []
                try:
                    args, kwargs = self._resolve_args_proc(
                        spec.args, spec.kwargs, pinned)
                    if spec.streaming:
                        self._execute_in_process_stream(
                            spec, args, kwargs, cancelled_event)
                    else:
                        self._execute_in_process(spec, args, kwargs,
                                                 cancelled_event)
                finally:
                    self._unpin_shm_keys(pinned)
            else:
                args, kwargs = _resolve_args(
                    self._store, spec.args, spec.kwargs)
                worker_mod._task_context.current_task_id = spec.task_id
                worker_mod._task_context.task_name = spec.name
                # Task-stuck watchdog feed (thread execution plane —
                # the process plane's twin lives in worker_main).
                from ray_tpu._private import flight as _flight

                if _flight._FLIGHT is not None:
                    _flight.note_task_started(spec.name)
                try:
                    renv = spec.runtime_env
                    if renv is not None and (renv.get("pip")
                                             or renv.get("uv")):
                        # Thread-plane workers share the driver
                        # interpreter; a venv-backed env cannot apply.
                        raise RuntimeEnvSetupError(
                            "pip/uv runtime envs need process workers "
                            "(worker_mode='process', the default)")

                    def _invoke():
                        result = spec.function(*args, **kwargs)
                        if spec.streaming:
                            # Yield loop runs inside the env context so
                            # the generator BODY sees the runtime env.
                            self._stream_outputs(spec, result,
                                                 cancelled_event)
                        return result

                    if renv is not None:
                        with renv.stage().applied():
                            result = _invoke()
                    else:
                        result = _invoke()
                finally:
                    worker_mod._task_context.current_task_id = None
                    worker_mod._task_context.task_name = None
                    if _flight._FLIGHT is not None:
                        _flight.note_task_finished()
                if not spec.streaming:
                    self._store_outputs(spec, result)
            if self._events:
                self._events.record(
                    spec.task_id, "FINISHED", name=spec.name,
                    duration=time.monotonic() - start)
            # A memory-monitor kill that raced this completion must not
            # leave a stale marker to mislabel a later failure.
            self._oom_killed.discard(spec.task_id)
            self._finalize_native(spec)
        except Exception as exc:  # noqa: BLE001 — task error boundary
            retry_spec = self._handle_failure(spec, exc)
            if retry_spec is None:
                self._finalize_native(spec)  # error outputs are final
        finally:
            with self._lock:
                self._running.pop(spec.task_id, None)
                self._backlog -= 1
                self._num_finished += 1
            self._resources.release(spec.resources)
            # Enqueue the retry only after this attempt's bookkeeping is
            # gone, so the retry's _running entry can't be popped by us.
            if retry_spec is not None:
                with self._lock:
                    self._backlog += 1
                    self._make_runnable_locked(retry_spec)
        return self._pick_next_inline()

    def _resolve_args_proc(self, args, kwargs, pinned: list):
        """Arg resolution for the process plane: a ref whose value is
        already resident in the shm store passes AS A SHM KEY — the worker
        reads it directly, no driver round-trip (plasma-parity data path).
        Everything else resolves to values like the thread path (raising
        on upstream task errors). Keys used are appended to ``pinned``
        (even on a mid-resolution raise) and must be unpinned by the
        caller after dispatch."""
        from ray_tpu._private.worker import ObjectRef, global_worker
        from ray_tpu._private.worker_main import _ShmRef

        ctx = global_worker().serialization_context

        def _resolve(v):
            if not isinstance(v, ObjectRef):
                return v
            key = self._shm_resident.get(v.object_id)
            if key is not None:
                with self._pin_lock:
                    # Pin before the existence check: the flush valve
                    # skips pinned keys, so a pinned+present key stays
                    # valid until the task's dispatch completes.
                    self._shm_key_pins[key] = (
                        self._shm_key_pins.get(key, 0) + 1)
                pinned.append(key)
                if self._shm_store.contains(key):
                    return _ShmRef(key)
            serialized = self._store.get(v.object_id)
            value = ctx.deserialize(serialized)
            if isinstance(value, RayTaskError):
                raise value.as_instanceof_cause()
            return value

        return (tuple(_resolve(a) for a in args),
                {k: _resolve(v) for k, v in kwargs.items()})

    def _unpin_shm_keys(self, pinned: list):
        with self._pin_lock:
            self._pin_cv.notify_all()
            for key in pinned:
                n = self._shm_key_pins.get(key, 0) - 1
                if n <= 0:
                    self._shm_key_pins.pop(key, None)
                    if key in self._deferred_deletes:
                        # Deferred by _clear_ret_keys mid-read. Delete
                        # UNDER the pin lock: resolvers pin before their
                        # contains() check, so an unpinned key here
                        # cannot acquire a new reader before the delete
                        # (same invariant as _maybe_flush_residents).
                        self._deferred_deletes.discard(key)
                        try:
                            self._shm_store.delete(key)
                        except Exception:  # noqa: BLE001 — reclaimed
                            pass
                else:
                    self._shm_key_pins[key] = n

    def _clear_ret_keys(self, keys, wait_for_reuse_s: float = 0.0):
        """Delete stale ret keys WITHOUT breaking the pin invariant: a
        key a consumer is reading right now is deferred — deleted at
        unpin — rather than yanked mid-read. Check-and-delete happens
        under the pin lock, mirroring _maybe_flush_residents, so a reader
        cannot pin between the check and the delete.

        Scheduler retries never reuse these slots (ret keys are salted by
        attempt), but LINEAGE re-execution re-submits with the SAME
        attempt — its worker must be able to re-put the key. Pass
        ``wait_for_reuse_s`` > 0 on that path: briefly wait for readers
        to unpin so the slot actually frees; if one outlasts the wait,
        the worker's put fails 'exists' (retriable) instead of the
        reader seeing torn bytes."""
        deadline = time.monotonic() + wait_for_reuse_s
        remaining = list(keys)
        while True:
            still = []
            for key in remaining:
                with self._pin_lock:
                    if key in self._shm_key_pins:
                        self._deferred_deletes.add(key)
                        still.append(key)
                        continue
                    self._deferred_deletes.discard(key)
                    try:
                        self._shm_store.delete(key)
                    except Exception as exc:  # not present
                        log.debug("ret-key %s already gone: %r", key,
                                  exc)
            remaining = still
            if not remaining:
                return
            left = deadline - time.monotonic()
            if left <= 0:
                return
            # Event-driven: an unpin notifies; the timeout only bounds a
            # reader that never unpins within the wait budget.
            with self._pin_cv:
                self._pin_cv.wait(min(left, 0.1))

    @staticmethod
    def _ret_key(oid, attempt: int) -> int:
        """Shm slot for one return of one attempt. Salting by attempt
        means a retry writes FRESH slots: a consumer still pinned to a
        prior attempt's output can finish its read (the stale slot is
        deferred-deleted at unpin) while the retry proceeds — no
        'exists' collision, no yank mid-read."""
        from ray_tpu._private.worker_pool import oid_key

        base = oid_key(oid)
        if attempt:
            base ^= (attempt * 0x9E37_79B9_7F4A_7C15)
        return base & 0x0FFF_FFFF_FFFF_FFFF

    def _maybe_flush_residents(self):
        """Pressure valve: residency is a read-through cache (the python
        store keeps the authoritative copy), so under shm pressure the
        oldest unpinned half is safely dropped rather than starving new
        results. Pinned keys (handed to an in-flight task as _ShmRef
        args) are never flushed."""
        try:
            stats = self._shm_store.stats()
        except Exception:  # noqa: BLE001 — store torn down
            return
        if stats["used"] <= stats["capacity"] * 0.6:
            return
        items = list(self._shm_resident.items())  # GIL-atomic snapshot
        for oid, key in items[:len(items) // 2]:
            with self._pin_lock:
                # Pin check AND delete under the pin lock: resolvers pin
                # before their contains() check, so a key observed
                # unpinned here cannot acquire a new reader between the
                # check and the delete.
                if key in self._shm_key_pins:
                    continue
                self._shm_resident.pop(oid, None)
                try:
                    self._shm_store.delete(key)
                except Exception:  # noqa: BLE001
                    pass

    def _release_shm_resident(self, object_id):
        """Evict callback from the python store — runs UNDER the store's
        lock, so only GIL-atomic dict ops and the leaf pin-lock here."""
        key = self._shm_resident.pop(object_id, None)
        if key is None or self._shm_store is None:
            return
        with self._pin_lock:
            if key in self._shm_key_pins:
                return  # in-flight arg: the python-store copy is gone but
                # the shm bytes stay until the dispatch unpins (leaked
                # only if the store evicts mid-dispatch — bounded).
        try:
            self._shm_store.delete(key)
        except Exception:  # noqa: BLE001 — already reclaimed
            pass

    def _lease_for(self, spec: TaskSpec):
        """The process a task runs in: a pooled CPU-pinned worker, or —
        for a task that holds ``TPU`` (already reserved in the resource
        pool by dispatch) — a worker of its own that is shown exactly
        the chips taken here. Returns ``(worker, chips)``."""
        from ray_tpu._private.tpu_chips import whole_chips
        from ray_tpu._private.worker import global_worker

        n = whole_chips(spec.resources)
        chips = (global_worker().chips.take(n, f"task {spec.name}")
                 if n else ())
        try:
            return self._worker_pool.lease(
                runtime_env=spec.runtime_env, tpu_chips=chips), chips
        except BaseException:
            global_worker().chips.give_back(chips)
            raise

    def _release_for(self, w, chips):
        from ray_tpu._private.worker import global_worker

        self._worker_pool.release(w)  # a chip worker exits here
        global_worker().chips.give_back(chips)

    def _execute_in_process(self, spec: TaskSpec, args, kwargs,
                            cancelled_event):
        """Ship the task to a leased worker process; outputs come back
        through the shm store (WorkerPool plane)."""
        from ray_tpu._private.serialization import SerializedObject
        from ray_tpu._private.worker import global_worker
        from ray_tpu._private.worker_pool import (
            pack_args,
            pack_function,
        )

        from ray_tpu._private.worker_pool import maybe_stage

        ctx = global_worker().serialization_context
        w, chips = self._lease_for(spec)
        staged: list = []
        ret_keys = [self._ret_key(oid, spec.attempt)
                    for oid in spec.return_ids]
        try:
            digest, fn_bytes = pack_function(spec.function)
            payload, staged = pack_args(self._shm_store, ctx, args, kwargs)
            # Oversized fields ride the store, not the (1MB) channel.
            limit = max(w.max_msg // 4, 64 * 1024)
            fn_bytes, st = maybe_stage(self._shm_store, fn_bytes, limit)
            staged += st
            payload, st = maybe_stage(self._shm_store, payload, limit)
            staged += st
            # A prior attempt may have died AFTER storing outputs but
            # BEFORE replying; clear this attempt's and the previous
            # attempt's stale slots (pin-respecting, deferred if a reader
            # is mid-flight) so the arena doesn't leak across retries,
            # and drop stale residency from lineage re-execution.
            for oid in spec.return_ids:
                self._shm_resident.pop(oid, None)
            # Current-attempt keys must actually free (lineage re-execution
            # reuses the attempt number, so its worker re-puts the SAME
            # key): wait briefly for readers. Prior-attempt slots are
            # never rewritten — pure deferral is fine.
            self._clear_ret_keys(ret_keys, wait_for_reuse_s=1.0)
            if spec.attempt > 0:
                self._clear_ret_keys(
                    [self._ret_key(oid, spec.attempt - 1)
                     for oid in spec.return_ids])
            with self._lock:
                self._proc_running[spec.task_id] = w
            try:
                env_fields = (dict(spec.runtime_env)
                              if spec.runtime_env is not None else None)
                msg = ("task", digest, fn_bytes, payload, ret_keys,
                       spec.num_returns, spec.task_id.binary(), spec.name,
                       env_fields)
                if spec.trace is not None and tracing._TRACER is not None:
                    # Optional trailing field (tracing off = message
                    # unchanged): the worker process records its own
                    # exec span under the task's trace context.
                    msg = msg + (tuple(spec.trace),)
                w.request(msg, cancel_event=cancelled_event)
            finally:
                with self._lock:
                    self._proc_running.pop(spec.task_id, None)
            for oid, key in zip(spec.return_ids, ret_keys):
                raw = bytes(self._shm_store.get(key))
                self._store.put(oid, SerializedObject.from_bytes(raw))
                # Outputs STAY shm-resident so downstream process tasks
                # read them in place; released when the python store
                # evicts the object.
                self._shm_resident[oid] = key
            self._maybe_flush_residents()
        except BaseException:
            # Failure path: a crashed worker may have left some ret keys
            # behind — reclaim the shm slots (pins respected: a consumer
            # mid-read defers the delete to its unpin).
            self._clear_ret_keys(ret_keys)
            raise
        finally:
            self._delete_shm_keys(staged)
            self._release_for(w, chips)

    def _delete_shm_keys(self, keys):
        for key in keys:
            try:
                self._shm_store.delete(key)
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass

    # ------------------------------------------------------------- streaming
    def _stream_outputs(self, spec: TaskSpec, result: Any, cancelled_event):
        """Thread-plane yield loop: each yield commits one dynamically
        created return object IMMEDIATELY (the consumer's next() unblocks
        on it), then the producer pauses while committed-but-unconsumed
        items have reached the backpressure budget. Cancellation (dropped
        generator / explicit cancel) stops the loop cooperatively between
        yields. Lineage re-execution replays from yield 0; already-
        committed indices re-put idempotently, so consumed items keep
        their first-attempt values (dedup by construction)."""
        from ray_tpu._private.streaming import stream_end_id, stream_item_id
        from ray_tpu._private.worker import global_worker

        if not hasattr(result, "__iter__") and \
                not hasattr(result, "__next__"):
            raise TypeError(
                f"task {spec.name!r} declared num_returns='streaming' but "
                f"returned non-iterable {type(result).__name__}")
        worker = global_worker()
        ctx = worker.serialization_context
        stream = worker.streams.get_or_create(spec.task_id)
        it = iter(result)
        idx = 0
        try:
            for item in it:
                if cancelled_event.is_set() or stream.cancelled:
                    raise TaskCancelledError(spec.task_id)
                self._store.put(stream_item_id(spec.task_id, idx),
                                ctx.serialize(item))
                stream.commit(idx)
                idx += 1
                if not stream.wait_capacity(spec.backpressure,
                                            cancelled_event):
                    raise TaskCancelledError(spec.task_id)
        except BaseException:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — generator cleanup
                    pass
            raise
        self._store.put(stream_end_id(spec.task_id), ctx.serialize(idx))
        stream.finish(idx)

    def _execute_in_process_stream(self, spec: TaskSpec, args, kwargs,
                                   cancelled_event):
        """Process-plane streaming: ship a ``task_stream`` request to a
        leased worker, then pump its reply channel — each ``item`` frame
        commits one return object into the driver store as the worker
        yields; consumption acks travel back on the worker's stream-ack
        channel (the pause protocol lives in worker_main). A kill -9 of
        the worker mid-stream surfaces WorkerCrashedError (retriable:
        lineage replay re-runs the generator from yield 0)."""
        from ray_tpu._private.worker import global_worker
        from ray_tpu._private.worker_pool import (
            maybe_stage,
            pack_args,
            pack_function,
        )

        ctx = global_worker().serialization_context
        stream = global_worker().streams.get_or_create(spec.task_id)
        w, chips = self._lease_for(spec)
        staged: list = []
        try:
            digest, fn_bytes = pack_function(spec.function)
            payload, staged = pack_args(self._shm_store, ctx, args, kwargs)
            limit = max(w.max_msg // 4, 64 * 1024)
            fn_bytes, st = maybe_stage(self._shm_store, fn_bytes, limit)
            staged += st
            payload, st = maybe_stage(self._shm_store, payload, limit)
            staged += st
            env_fields = (dict(spec.runtime_env)
                          if spec.runtime_env is not None else None)
            with self._lock:
                self._proc_running[spec.task_id] = w
            try:
                w._req.write(
                    ("task_stream", digest, fn_bytes, payload,
                     spec.task_id.binary(), spec.name, env_fields,
                     int(spec.backpressure)), timeout=60.0)
                pump_stream_replies(
                    w, spec.task_id, spec.name, stream, self._store,
                    self._shm_store, ctx, cancelled_event)
            finally:
                with self._lock:
                    self._proc_running.pop(spec.task_id, None)
        finally:
            self._delete_shm_keys(staged)
            self._release_for(w, chips)

    def _store_outputs(self, spec: TaskSpec, result: Any):
        from ray_tpu._private.worker import global_worker

        ctx = global_worker().serialization_context
        if spec.num_returns <= 1:
            outputs = [result]
        else:
            outputs = list(result)
            if len(outputs) != spec.num_returns:
                raise ValueError(
                    f"task {spec.name!r} declared num_returns="
                    f"{spec.num_returns} but returned {len(outputs)} values"
                )
        for oid, value in zip(spec.return_ids, outputs):
            self._store.put(oid, ctx.serialize(value))

    def _handle_failure(self, spec: TaskSpec, exc: Exception):
        # Worker-process death is a system failure: retriable by default,
        # like the reference's WorkerCrashedError semantics.
        from ray_tpu.exceptions import (
            OutOfMemoryError,
            WorkerCrashedError,
            WorkerPoolExhaustedError,
        )

        if spec.task_id in self._oom_killed:
            self._oom_killed.discard(spec.task_id)
            exc = OutOfMemoryError(
                f"task {spec.name!r} was killed by the memory monitor "
                f"(system memory pressure; youngest-task-first policy)")
        is_app_error = not isinstance(
            exc, (SystemError, MemoryError, OutOfMemoryError,
                  WorkerCrashedError, WorkerPoolExhaustedError))
        retriable = spec.attempt < spec.max_retries and (
            spec.retry_exceptions or not is_app_error
        )
        cancelled = isinstance(exc, TaskCancelledError)
        if self._events:
            self._events.record(spec.task_id, "FAILED", name=spec.name)
        if retriable and not cancelled:
            import dataclasses

            return dataclasses.replace(spec, attempt=spec.attempt + 1)
        if isinstance(exc, (TaskCancelledError, RayTaskError,
                            OutOfMemoryError)):
            error = exc  # typed system/dependency failures stay unwrapped
        else:
            error = RayTaskError.from_exception(spec.name, exc)
        for oid in spec.return_ids:
            self._store.put_error(oid, error)
        if spec.streaming:
            self._fail_stream(spec, error)

    def _fail_stream(self, spec: TaskSpec, error: BaseException):
        """Terminal streaming failure: record it on the stream state so a
        paused producer/consumer wakes, and release the replay barrier."""
        from ray_tpu._private.worker import _try_global_worker

        w = _try_global_worker()
        if w is None:
            return
        stream = w.streams.get(spec.task_id)
        if stream is not None:
            stream.set_error(error)

    def _finish_cancelled(self, spec: TaskSpec):
        err = TaskCancelledError(spec.task_id)
        for oid in spec.return_ids:
            self._store.put_error(oid, err)
        self._finalize_native(spec)
        with self._lock:
            self._backlog -= 1

    # ----------------------------------------------------------- cancel/misc
    def cancel(self, task_id: TaskID, force: bool = False):
        """Cancel a task.

        Queued (runnable) tasks are removed immediately; running tasks get
        the cooperative cancel event (force=True additionally kills the
        worker process so the task actually stops). A task still PENDING
        in the native ready-ring is cancelled lazily: the ring has no
        removal op, so the task is discarded when it pops — consumers of
        its outputs observe TaskCancelledError at that point rather than
        instantly (deferred-cancel semantics).
        """
        with self._lock:
            self._cancelled.add(task_id)
            found = None
            for key, dq in self._runnable.items():
                for i, (_, spec) in enumerate(dq):
                    if spec.task_id == task_id:
                        found = (key, i, spec)
                        break
                if found:
                    break
            if found:
                key, i, spec = found
                dq = self._runnable[key]
                del dq[i]
                if not dq:
                    del self._runnable[key]
                self._runnable_count -= 1
                threading.Thread(
                    target=self._finish_cancelled, args=(spec,),
                    daemon=True,
                ).start()
                return True
            ev = self._running.get(task_id)
            proc = self._proc_running.get(task_id)
            if ev is not None:
                ev.set()  # cooperative: running tasks can poll was_cancelled
                if force and proc is not None:
                    # Process plane: force-cancel actually stops the task by
                    # killing its worker (the pool replaces it); the waiting
                    # executor observes the cancel event and reports
                    # TaskCancelledError rather than a crash.
                    proc.kill()
                    return True
                return False
        # Not queued and not running: either not yet dep-resolved or done.
        return False

    def lineage_for(self, task_id: TaskID) -> Optional[TaskSpec]:
        return self._lineage.get(task_id)

    def backlog_size(self) -> int:
        with self._lock:
            return self._backlog

    def num_running(self) -> int:
        """Tasks currently EXECUTING (backlog minus these = queued)."""
        with self._lock:
            return len(self._running)

    def num_finished(self) -> int:
        with self._lock:
            return self._num_finished

    def shutdown(self):
        if self._shm_store is not None:
            self._store.remove_evict_callback(self._release_shm_resident)
        with self._lock:
            self._shutdown = True
            self._dispatch_cv.notify_all()
        self._dispatcher.join(timeout=2)
        if self._dq is not None:
            # Wake + join the pump so it can't be blocked inside rtn_dq_pop
            # when the queue's destructor frees the native state.
            self._dq.wake()
            self._dq_pump.join(timeout=2)
        self._pool.shutdown(wait=False, cancel_futures=True)


def pump_stream_replies(w, task_id, name: str, stream, store, shm_store,
                        ctx, cancelled_event=None):
    """Driver-side pump for one process-plane stream (shared by the task
    scheduler and sync process actors): read ``item`` frames off the
    worker's reply channel into the local store, forward consumption acks
    on the stream-ack channel (coalesced — only the latest watermark
    matters), and translate worker death into WorkerCrashedError. Returns
    the total item count on clean completion."""
    import pickle as _pickle

    from ray_tpu._private.serialization import SerializedObject
    from ray_tpu._private.streaming import stream_end_id, stream_item_id
    from ray_tpu.exceptions import (
        ChannelTimeoutError,
        WorkerCrashedError,
    )

    tid_bin = task_id.binary()
    last_acked = [0]
    done = threading.Event()

    def _send_ack(n: int) -> bool:
        if done.is_set():
            return False
        try:
            w._ack.write(("stream_ack", tid_bin, n), timeout=0.05)
            if n > last_acked[0]:
                last_acked[0] = n
            return True
        except Exception:  # noqa: BLE001 — pump retries with the latest
            return False

    # Immediate ack from the consumer thread keeps resume latency off the
    # pump's read-slice cadence; the pump loop below is the retry path.
    stream.add_consume_listener(_send_ack)
    cancel_sent = [False]

    def _drain_after_error():
        """Driver-side failure while the worker is alive and mid-stream
        (e.g. a staged item key evicted, the local store put failing):
        the reply channel still carries item/terminal frames, and
        releasing the worker now would desync the next lease's reply
        protocol. Cancel cooperatively and drain to the terminal frame;
        a worker that will not settle is condemned so the pool replaces
        it instead of reusing a dirty channel."""
        _send_ack(-1)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                m = w._rep.read(timeout=0.25)
            except ChannelTimeoutError:
                if w.proc.poll() is not None:
                    w._dead = True
                    return
                _send_ack(-1)
                continue
            except Exception as exc:  # channel torn down
                log.debug("drain-after-error read failed; condemning "
                          "worker: %r", exc)
                break
            if m and m[0] in ("ok", "cancelled", "err"):
                return
        w._dead = True
    try:
        while True:
            cancelled_now = ((cancelled_event is not None
                              and cancelled_event.is_set())
                             or stream.cancelled)
            if cancelled_now and not cancel_sent[0]:
                cancel_sent[0] = _send_ack(-1)  # -1 = cooperative cancel
            if stream.consumed > last_acked[0]:
                _send_ack(stream.consumed)
            try:
                msg = w._rep.read(timeout=0.05)
            except ChannelTimeoutError:
                if w.proc.poll() is not None:
                    w._dead = True
                    if cancelled_now:
                        raise TaskCancelledError(task_id)
                    raise WorkerCrashedError(
                        f"worker {w.pid} died mid-stream of task "
                        f"{name!r} (exit code {w.proc.returncode})")
                continue
            kind = msg[0]
            if kind == "item":
                try:
                    _, idx, field = msg
                    if isinstance(field, tuple) and field and \
                            field[0] == "shm":
                        raw = bytes(shm_store.get(field[1]))
                        try:
                            shm_store.delete(field[1])
                        except Exception as exc:  # staged key raced away
                            log.debug("staged stream item %s already "
                                      "deleted: %r", field[1], exc)
                    else:
                        raw = bytes(field)
                    store.put(stream_item_id(task_id, idx),
                              SerializedObject.from_bytes(raw))
                    stream.commit(idx)
                except BaseException:
                    _drain_after_error()
                    raise
            elif kind == "ok":
                total = int(msg[1])
                store.put(stream_end_id(task_id), ctx.serialize(total))
                stream.finish(total)
                return total
            elif kind == "cancelled":
                raise TaskCancelledError(task_id)
            elif kind == "err":
                raise _pickle.loads(msg[1])
            # Anything else (stale frame from a crashed predecessor) is
            # dropped; the liveness check above bounds the stall.
    finally:
        done.set()


def _shape_key(resources: Dict[str, float]) -> tuple:
    """Hashable resource-demand shape (dispatch bucket key)."""
    return tuple(sorted(resources.items()))


def _collect_refs(args, kwargs) -> list:
    """Top-level ObjectRef args are awaited + inlined (reference semantics:
    nested refs inside structures are NOT resolved)."""
    from ray_tpu._private.worker import ObjectRef

    refs = [a for a in args if isinstance(a, ObjectRef)]
    refs += [v for v in kwargs.values() if isinstance(v, ObjectRef)]
    return refs


def _resolve_args(store, args, kwargs):
    from ray_tpu._private.worker import ObjectRef, global_worker

    ctx = global_worker().serialization_context

    def _resolve(v):
        if isinstance(v, ObjectRef):
            serialized = store.get(v.object_id)
            value = ctx.deserialize(serialized)
            if isinstance(value, RayTaskError):
                raise value.as_instanceof_cause()
            return value
        return v

    return (
        tuple(_resolve(a) for a in args),
        {k: _resolve(v) for k, v in kwargs.items()},
    )
