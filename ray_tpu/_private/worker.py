"""Global worker: init/shutdown, ObjectRef, get/put/wait/cancel.

Rebuild of the reference's worker core (reference:
python/ray/_private/worker.py + the Cython CoreWorker it wraps [unverified]).
One process-global ``Worker`` owns the serialization context, object store,
local scheduler, actor registry, and task-event buffer; ``init()`` boots it
and ``shutdown()`` tears it down. ObjectRefs count local references on
construction/destruction (owner-side refcounting).
"""

from __future__ import annotations

import atexit
import os
import tempfile
import threading
import uuid
from typing import Any, Dict, List, Optional, Union

from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import (
    JobID,
    NodeID,
    ObjectID,
    TaskID,
    WorkerID,
    _Counter,
)
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.scheduler import LocalScheduler, ResourcePool, TaskSpec
from ray_tpu._private.log import get_logger
from ray_tpu._private.serialization import SerializationContext
from ray_tpu._private.task_events import TaskEventBuffer
from ray_tpu._private import tracing
from ray_tpu.exceptions import RayTaskError, RayTpuError

log = get_logger(__name__)

class _TaskContext:
    """Per-execution task context. Backed by contextvars rather than
    threading.local so ASYNC actor calls — many coroutines interleaving
    on one event-loop thread — each see their own task id across await
    points (asyncio tasks run in copied contexts). For plain threads the
    semantics match threading.local: each thread's sets are isolated."""

    __slots__ = ("_tid", "_name")

    def __init__(self):
        import contextvars

        object.__setattr__(self, "_tid", contextvars.ContextVar(
            "ray_tpu_task_id", default=None))
        object.__setattr__(self, "_name", contextvars.ContextVar(
            "ray_tpu_task_name", default=None))

    @property
    def current_task_id(self):
        return self._tid.get()

    @current_task_id.setter
    def current_task_id(self, value):
        self._tid.set(value)

    @property
    def task_name(self):
        return self._name.get()

    @task_name.setter
    def task_name(self, value):
        self._name.set(value)


_task_context = _TaskContext()


class ObjectRef:
    """Future handle to a task output or put object.

    Pickling an ObjectRef registers the serialization with the owner store so
    the object stays alive while borrowed (simplified borrower protocol).
    """

    __slots__ = ("object_id", "_owner", "__weakref__")

    def __init__(self, object_id: ObjectID, _add_ref: bool = True):
        self.object_id = object_id
        self._owner = _try_global_worker()
        if _add_ref and self._owner is not None:
            self._owner.store.add_local_ref(object_id)

    def hex(self) -> str:
        return self.object_id.hex()

    def task_id(self) -> TaskID:
        return self.object_id.task_id()

    def future(self):
        """Return a concurrent.futures.Future resolving to the value."""
        import concurrent.futures

        fut: concurrent.futures.Future = concurrent.futures.Future()
        worker = global_worker()

        def _done():
            try:
                fut.set_result(worker.get_object(self))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        worker.store.on_ready(self.object_id, _done)
        return fut

    def __await__(self):
        import asyncio

        loop = asyncio.get_event_loop()
        afut = loop.create_future()
        worker = global_worker()

        def _done():
            def _set():
                if afut.cancelled():
                    return
                try:
                    afut.set_result(worker.get_object(self))
                except BaseException as e:  # noqa: BLE001
                    afut.set_exception(e)

            loop.call_soon_threadsafe(_set)

        worker.store.on_ready(self.object_id, _done)
        return afut.__await__()

    def __reduce__(self):
        w = _try_global_worker()
        owner_info = None
        if w is not None:
            # Borrowed: keep alive for the borrower's lifetime (simplified —
            # the reference tracks borrowers and releases on their exit).
            w.store.add_local_ref(self.object_id)
            # Ownership model: a serialized ref carries its OWNER's
            # identity + direct address, so a foreign deserializer
            # resolves/subscribes owner-direct instead of polling the
            # head. A ref this runtime itself borrowed propagates the
            # ORIGINAL owner, not the forwarder. (Process-plane worker
            # stubs have no head client — their refs stay owner-less.)
            hc = getattr(w, "head_client", None)
            if hc is not None:
                owner_info = w.borrowed_owner(self.object_id.binary()) \
                    or (hc.client_id, list(hc._object_server.address))
        return (_deserialize_ref, (self.object_id, owner_info))

    def __del__(self):
        w = self._owner
        if w is not None and w.is_alive:
            try:
                w.store.remove_local_ref(self.object_id)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass

    def __hash__(self):
        return hash(self.object_id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.object_id == self.object_id

    def __repr__(self):
        return f"ObjectRef({self.object_id.hex()[:16]}…)"


def _deserialize_ref(object_id: ObjectID, owner_info=None) -> ObjectRef:
    w = _try_global_worker()
    if owner_info is not None and w is not None \
            and getattr(w, "head_client", None) is not None \
            and owner_info[0] != w.head_client.client_id:
        w.record_borrowed_owner(object_id.binary(), owner_info)
    return ObjectRef(object_id, _add_ref=False)


class ObjectRefGenerator:
    """Iterator over the item ObjectRefs of a ``num_returns="streaming"``
    task (reference parity: ``ObjectRefGenerator``). Each ``next()``
    blocks only until the NEXT yield's object commits — locally, or via
    its ``item_done`` report from the executing node — not until the
    whole task finishes; returning a ref counts as CONSUMPTION for the
    producer's backpressure budget. ``close()`` (or dropping the
    generator) cancels the in-flight task and releases
    committed-but-unconsumed items. Mid-stream producer death surfaces
    the typed error at the next ``next()`` (after lineage replay, if
    any, is exhausted)."""

    def __init__(self, task_id: TaskID, worker: "Worker"):
        from ray_tpu._private.streaming import stream_end_id

        self._task_id = task_id
        self._worker = worker
        self._stream = worker.streams.get_or_create(task_id)
        self._index = 0
        self._end_oid = stream_end_id(task_id)
        self._end_ref = ObjectRef(self._end_oid)
        self._pending_ref: Optional[ObjectRef] = None
        self._total: Optional[int] = None
        self._closed = False

    # ------------------------------------------------------------ iteration
    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        ref = self._next(block=True)
        assert ref is not None
        return ref

    def try_next(self) -> Optional[ObjectRef]:
        """Non-blocking ``next()``: the next item's ref if it is already
        committed locally, else None. Raises StopIteration / the task's
        typed error exactly like ``next()``."""
        return self._next(block=False)

    def completed(self) -> ObjectRef:
        """The stream's END MARKER ref: ready when the whole generator
        task finished (value = total yield count; errors raise)."""
        return self._end_ref

    def wait_refs(self) -> List[ObjectRef]:
        """Refs to pass to ``ray_tpu.wait`` for "the next ``next()``
        would make progress": the NEXT item's ref plus the end marker.
        Lets a scheduler multiplex many streams without blocking on any
        single one."""
        return [self._item_ref(), self._end_ref]

    @property
    def task_id(self) -> TaskID:
        return self._task_id

    def _item_ref(self) -> ObjectRef:
        """The (cached) ref for the CURRENT index — handed out by the
        next successful ``next()``, so creating it early (for waits)
        leaks nothing."""
        from ray_tpu._private.streaming import stream_item_id

        if self._pending_ref is None:
            self._pending_ref = ObjectRef(
                stream_item_id(self._task_id, self._index))
        return self._pending_ref

    def _read_total(self) -> int:
        """The committed end marker: total count, or the task's typed
        error re-raised."""
        serialized = self._worker.store.get(self._end_oid, timeout=5.0)
        value = self._worker.serialization_context.deserialize(serialized)
        if isinstance(value, RayTaskError):
            raise value.as_instanceof_cause()
        return int(value)

    def _free_unconsumed(self):
        """Release committed-but-unconsumed item payloads (everything
        from the consumer's cursor up to the committed/total high-water
        mark) — the shared teardown step of close() and _fail_closed()."""
        from ray_tpu._private.streaming import stream_item_id

        upper = self._stream.committed
        if self._total is not None:
            upper = max(upper, self._total)
        drop = [stream_item_id(self._task_id, i)
                for i in range(self._index, upper)]
        if drop:
            self._worker.store.free(drop)

    def _fail_closed(self):
        """Error-path teardown: the task already finished or failed, so
        there is nothing to cancel — but committed-but-unconsumed item
        payloads and the stream's registry entry must still go, or every
        errored stream pins them forever. Marks the generator closed so
        close()/__del__ become no-ops."""
        self._closed = True
        try:
            if self._worker.is_alive:
                self._free_unconsumed()
        except Exception:  # noqa: BLE001 — cleanup must not mask the error
            pass
        finally:
            self._release_stream()

    def _next(self, block: bool) -> Optional[ObjectRef]:
        import time as _time

        if self._closed:
            raise StopIteration
        store = self._worker.store
        end_grace: Optional[float] = None
        while True:
            item = self._item_ref()
            oid = item.object_id
            if store.is_ready(oid):
                err = store.peek_error(oid)
                if err is not None:
                    self._fail_closed()
                    if hasattr(err, "as_instanceof_cause"):
                        raise err.as_instanceof_cause()
                    raise err
                self._pending_ref = None
                self._index += 1
                self._stream.advance_consumed(self._index)
                return item
            if self._total is None and store.is_ready(self._end_oid):
                try:
                    self._total = self._read_total()
                except BaseException:
                    self._fail_closed()
                    raise
            if self._total is not None and self._index >= self._total:
                self._closed = True
                self._release_stream()
                raise StopIteration
            if not block:
                return None
            # Remote streams: a large item's bytes stayed on the
            # producing node (announce + pull) — drive the transfer.
            router = self._worker.remote_router
            if router is not None and router.handles(oid) and \
                    self._index in self._stream.known_remote_sizes:
                router.prefetch(oid)
            if self._total is not None:
                # Task DONE but item i < total is not local: its bytes
                # are still in flight (pull) — or lost with no producer
                # left. Bound the wait so a lost item cannot hang us.
                if end_grace is None:
                    end_grace = _time.monotonic() + (
                        30.0 if router is not None else 5.0)
                elif _time.monotonic() > end_grace:
                    from ray_tpu.exceptions import ObjectLostError

                    self._fail_closed()
                    raise ObjectLostError(
                        f"streaming item {self._index} of task "
                        f"{self._task_id.hex()[:16]}… completed but its "
                        f"bytes are no longer retrievable")
            store.wait([oid, self._end_oid], 1, 0.2)

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Cancel the in-flight generator task and release
        committed-but-unconsumed items. Idempotent; also runs when the
        generator is garbage-collected before exhaustion."""
        if self._closed:
            return
        self._closed = True
        w = self._worker
        if not w.is_alive:
            return
        stream = self._stream
        try:
            if not w.store.is_ready(self._end_oid):
                stream.cancel()
                router = w.remote_router
                if router is not None and router.handles(self._end_oid):
                    router.cancel_stream(self._task_id)
                w.scheduler.cancel(self._task_id)
                # Materialize the typed cancellation end so any other
                # waiter (ray_tpu.wait on completed()) unblocks.
                w.store.cancel(self._end_oid, self._task_id)
            self._free_unconsumed()
        finally:
            self._release_stream()

    def _release_stream(self):
        self._worker.streams.pop(self._task_id)

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def __repr__(self):
        return (f"ObjectRefGenerator({self._task_id.hex()[:16]}…, "
                f"next={self._index})")


class Worker:
    def __init__(self, num_cpus: Optional[int] = None,
                 num_tpus: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None,
                 session_dir: Optional[str] = None,
                 worker_mode: Optional[str] = None,
                 head_address: Optional[str] = None):
        self.is_alive = True
        # Control plane: with an address, this driver joins the standalone
        # head service (GCS analogue) — KV becomes cluster-global, named
        # actors resolve across drivers, objects pull across drivers.
        self.head_client = None
        self.remote_router = None
        if head_address:
            from ray_tpu._private.head_client import HeadClient

            self.head_client = HeadClient(head_address)
        self.job_id = JobID.from_int(os.getpid() & 0xFFFFFFFF)
        self.worker_id = WorkerID.from_random()
        self.node_id = NodeID.from_random()
        self.driver_task_id = TaskID.for_driver(self.job_id)
        self.session_dir = session_dir or os.path.join(
            tempfile.gettempdir(), "ray_tpu",
            f"session_{uuid.uuid4().hex[:12]}")
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)
        # Distributed tracing (RAY_TPU_TRACE): arm the per-process span
        # ring, and point spawned worker processes (env inherits) at
        # this session's trace dir so their spans surface through our
        # trace_dump. One `is None` branch everywhere when off.
        if os.environ.get(tracing.ENV_VAR):
            # Always re-point at OUR session (a daemon inherits the
            # launching driver's env): each runtime's child workers
            # spill locally, surfaced by this process's trace_dump.
            os.environ[tracing.ENV_DIR] = os.path.join(
                self.session_dir, "traces")
        tracer = tracing.install_from_env(component="driver")
        if tracer is not None and self.head_client is not None:
            # Node-qualify this process — and, via the env, its spawned
            # worker processes — so assembled views keep same-pid
            # processes on different hosts distinct.
            tracer.set_identity(node=self.head_client.client_id)
            os.environ[tracing.ENV_NODE] = self.head_client.client_id
        # Flight recorder (RAY_TPU_FLIGHT / RAY_TPU_PROFILE): same
        # arming shape as tracing — point spawned worker processes at
        # this session's flight dir so their spilled bundles surface
        # through this runtime's debug_dump. An OPERATOR-set
        # RAY_TPU_FLIGHT_DIR is authoritative and survives; only dirs
        # a ray_tpu runtime auto-pointed (marked by the _AUTO
        # sentinel, e.g. a daemon inheriting the launching driver's
        # session path — wrong host, wrong session) are re-pointed.
        from ray_tpu._private import flight

        if (os.environ.get(flight.ENV_VAR)
                or os.environ.get(flight.ENV_PROFILE)):
            if (not os.environ.get(flight.ENV_DIR)
                    or os.environ.get(flight.ENV_DIR_AUTO)):
                os.environ[flight.ENV_DIR] = os.path.join(
                    self.session_dir, "flight")
                os.environ[flight.ENV_DIR_AUTO] = "1"
        rec = flight.install_from_env(component="driver")
        if rec is not None:
            rec.dump_dir = os.environ.get(flight.ENV_DIR, rec.dump_dir)
            if self.head_client is not None:
                rec.set_identity(node=self.head_client.client_id)
                os.environ[flight.ENV_NODE] = self.head_client.client_id
        # session_latest convenience link (the `logs` CLI default target).
        link = os.path.join(os.path.dirname(self.session_dir),
                            "session_latest")
        try:
            if os.path.islink(link):
                os.unlink(link)
            os.symlink(self.session_dir, link)
        except OSError:
            pass
        self.serialization_context = SerializationContext()
        spill_dir = GlobalConfig.object_spill_dir or os.path.join(
            self.session_dir, "spill")
        self.store = ObjectStore(spill_dir)
        # Streaming-generator plane: per-task stream state (yield commit
        # counters, backpressure watermarks) for num_returns="streaming".
        from ray_tpu._private.streaming import StreamRegistry

        self.streams = StreamRegistry()
        self.task_events = TaskEventBuffer(GlobalConfig.task_events_max_buffer)
        if num_cpus is None:
            num_cpus = os.cpu_count() or 1
        total = {"CPU": float(num_cpus)}
        # Chips are counted from the device nodes, never through JAX: a
        # driver that initialised a backend would own every chip, and
        # the worker processes that run the models could open none.
        from ray_tpu._private.tpu_chips import ChipTable, detect_num_chips

        if num_tpus is None:
            num_tpus = detect_num_chips()
        if num_tpus:
            total["TPU"] = float(num_tpus)
        total.update(resources or {})
        self.resource_pool = ResourcePool(total)
        self.chips = ChipTable(int(total.get("TPU", 0)))
        pool_size = GlobalConfig.worker_pool_size or max(int(num_cpus), 4)
        # Process execution plane: worker processes leased from a pool, fed
        # over the native shm store (reference: raylet WorkerPool + plasma).
        self.worker_mode = worker_mode or GlobalConfig.worker_mode
        self.shm_store = None
        self.worker_pool = None
        if self.worker_mode == "process":
            try:
                from ray_tpu._native.store import NativeObjectStore
                from ray_tpu._private.worker_pool import WorkerPool

                self.shm_store = NativeObjectStore.create(
                    capacity=GlobalConfig.shm_store_bytes,
                    max_objects=GlobalConfig.shm_store_slots)
                log_dir = os.path.join(self.session_dir, "logs")
                self.worker_pool = WorkerPool(
                    self.shm_store, num_workers=max(int(num_cpus), 1),
                    max_msg=GlobalConfig.worker_channel_bytes,
                    log_dir=log_dir, host_chips=self.chips.total)
                # Stream worker prints back to the driver (log plane).
                from ray_tpu._private.log_monitor import LogMonitor

                self.log_monitor = LogMonitor(log_dir)
            except Exception:  # noqa: BLE001 — no native toolchain: degrade
                # Release anything half-built: a created shm segment and
                # spawned worker processes must not outlive the fallback.
                if self.worker_pool is not None:
                    try:
                        self.worker_pool.shutdown()
                    except Exception:  # noqa: BLE001
                        pass
                if self.shm_store is not None:
                    try:
                        self.shm_store.close()
                    except Exception:  # noqa: BLE001
                        pass
                self.worker_mode = "thread"
                self.shm_store = None
                self.worker_pool = None
        self.scheduler = LocalScheduler(
            self.store, self.resource_pool, pool_size,
            task_events=self.task_events,
            worker_pool=self.worker_pool, shm_store=self.shm_store,
        )
        # Debug-mode host-plane sanitizer (RAY_TPU_SANITIZE=1): refcount
        # underflow + channel protocol checks hook in at their sites;
        # the stall watchdog needs the runtime handles.
        self.sanitizer_watchdog = None
        from ray_tpu.util import sanitizer as _sanitizer

        if _sanitizer.enabled():
            self.sanitizer_watchdog = _sanitizer.StallWatchdog(
                self.scheduler, self.resource_pool)
        # Flight-recorder section: scheduler/store depths render into
        # every local bundle (the "where is this process stuck" data).
        flight.add_section("runtime", self._flight_section)
        self.memory_monitor = None
        if (self.worker_pool is not None
                and GlobalConfig.memory_monitor_threshold > 0):
            from ray_tpu._private.memory_monitor import MemoryMonitor

            self.memory_monitor = MemoryMonitor(
                self.scheduler,
                threshold_fraction=GlobalConfig.memory_monitor_threshold)
        # Ownership plane: owners of refs borrowed FROM other drivers
        # (recorded at ref deserialization — serialized refs carry their
        # owner's identity + direct address).
        self.borrowed_owners: Dict[bytes, tuple] = {}
        self._borrowed_lock = threading.Lock()
        self.owner_resolver = None
        if self.head_client is not None:
            from ray_tpu._private.ownership import OwnerResolver
            from ray_tpu._private.remote_router import RemoteRouter

            self.remote_router = RemoteRouter(self)
            self.owner_resolver = OwnerResolver(self)
        self.submission_counter = _Counter()
        self.put_counter = _Counter()
        self.actor_counter = _Counter()
        self.actors: Dict[Any, Any] = {}  # ActorID -> _ActorRuntime
        self.named_actors: Dict[str, Any] = {}  # (namespace,name) -> handle
        self.placement_groups: Dict[Any, Any] = {}
        self._kv: Dict[bytes, bytes] = {}  # internal KV (GCS-KV parity)
        self._kv_lock = threading.Lock()
        if self.head_client is not None:
            # Head failover re-registration hook: when the client
            # observes a promoted head, this driver reconciles the
            # replayed directories with its live truth (named actors
            # it owns, cluster-actor placements it made).
            self.head_client.failover_callbacks.append(
                self._on_head_failover)

    def _on_head_failover(self, old_epoch: int, new_epoch: int) -> None:
        """Re-join announcements for a promoted head: re-register this
        driver's live named actors and re-place its live cluster
        actors. The promoted head replayed the shared log, so most
        entries already exist — re-registration by the same owner
        reconciles (overwrites) rather than conflicts, and entries
        lost in the dead primary's torn log tail reappear here."""
        hc = self.head_client
        if hc is None or not self.is_alive:
            return
        for (ns, name), handle in list(self.named_actors.items()):
            runtime = getattr(handle, "_runtime", None)
            if runtime is None or getattr(runtime, "dead", False):
                continue
            try:
                hc.actor_register(
                    ns, name, runtime.actor_id.binary(),
                    getattr(runtime, "class_name", "") or "")
            except Exception as exc:  # noqa: BLE001 — replayed entry
                log.warning("named-actor re-register of %r after "
                               "head failover failed (the replayed "
                               "directory entry still serves): %r",
                               name, exc)
        from ray_tpu._private.remote_actor import RemoteActorRuntime

        for runtime in list(self.actors.values()):
            if not isinstance(runtime, RemoteActorRuntime) \
                    or runtime.dead or runtime.borrower:
                continue
            try:
                hc.actor_place(runtime.actor_id.binary(), {
                    "node": runtime.node_client,
                    "driver": hc.client_id,
                    "cls": runtime._cls_bytes,
                    "class_name": runtime.class_name,
                    "detached":
                        runtime.opts.get("lifetime") == "detached",
                })
            except Exception as exc:  # noqa: BLE001 — same fallback
                log.warning("cluster-actor re-place after head "
                               "failover failed (replayed placement "
                               "still serves): %r", exc)

    def _flight_section(self) -> dict:
        """Runtime depths for this process's flight bundle: the
        queue/backlog numbers a postmortem reads first."""
        s = self.scheduler
        out = {
            "backlog": s.backlog_size(),
            "running": getattr(s, "num_running", lambda: 0)(),
            "finished": getattr(s, "num_finished", lambda: 0)(),
            "store_objects": len(getattr(self.store, "_entries", ())),
            "resources_available": self.resource_pool.available(),
            "worker_mode": self.worker_mode,
        }
        r = self.remote_router
        if r is not None:
            out["router"] = {
                "direct_pushes": getattr(r, "direct_pushes", 0),
                "relayed_pushes": getattr(r, "relayed_pushes", 0),
            }
        return out

    # ------------------------------------------------------------------- api
    def current_task_id(self) -> TaskID:
        tid = getattr(_task_context, "current_task_id", None)
        return tid if tid is not None else self.driver_task_id

    def next_task_id(self) -> TaskID:
        return TaskID.of(self.current_task_id(),
                         self.submission_counter.next())

    def put_object(self, value: Any) -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError(
                "Calling put() on an ObjectRef is not allowed; pass the ref "
                "directly instead.")
        oid = ObjectID.for_put(self.current_task_id(),
                               self.put_counter.next())
        serialized = self.serialization_context.serialize(value)
        self.store.put(oid, serialized)
        return ObjectRef(oid)

    def announce_object(self, ref: ObjectRef):
        """Publish this object's location to the head's object directory
        so other drivers can pull it (ObjectManager-relay analogue)."""
        if self.head_client is None:
            raise RayTpuError(
                "announce_object needs a head service "
                "(ray_tpu.init(address=...))")
        if not self.store.is_ready(ref.object_id):
            raise RayTpuError(
                "announce_object: the object is not materialized locally "
                "yet; ray_tpu.wait() on the ref first")
        self.head_client.object_announce(ref.object_id.binary())

    def _maybe_pull_from_head(self, object_id: ObjectID) -> None:
        """Cross-driver pull for objects with no local value and no known
        local producer. Refs of tasks this driver submitted resolve from
        lineage without a head round-trip; cross-driver refs (whether they
        arrived by pickle or were constructed from a hex id) pull once."""
        if self.head_client is None or self.store.is_ready(object_id):
            return
        if self.store.has_local_producer(object_id):
            return  # a local task/actor will produce it: never pullable
        if self.scheduler.lineage_for(object_id.task_id()) is not None:
            return  # a local task will produce it
        raw = self.head_client.object_pull(object_id.binary())
        if raw is not None:
            from ray_tpu._private.serialization import SerializedObject

            self.store.put(object_id, SerializedObject.from_bytes(raw))

    def record_borrowed_owner(self, oid_bin: bytes, owner_info):
        with self._borrowed_lock:
            if len(self.borrowed_owners) > 131072:
                # Hint table only (resolution falls back to the head):
                # recency-bounded via dict insertion order.
                self.borrowed_owners.pop(
                    next(iter(self.borrowed_owners)))
            self.borrowed_owners[oid_bin] = (
                owner_info[0], tuple(owner_info[1]))

    def borrowed_owner(self, oid_bin: bytes):
        with self._borrowed_lock:
            return self.borrowed_owners.get(oid_bin)

    def _pull_wait(self, object_id: ObjectID, timeout: Optional[float]):
        """Cross-driver resolve, event-driven end to end: a ref whose
        OWNER is known (serialized refs carry it) resolves/subscribes
        owner-direct over the p2p plane; an owner-less foreign ref (hex-
        constructed) subscribes to the head's ``obj|<hex>`` directory
        topic and re-pulls on announce — no poll loop either way. A
        typed ``GetTimeoutError`` materializes at the
        ``RAY_TPU_DEP_WAIT_S`` bound (or the caller's shorter timeout)."""
        import time as _time

        from ray_tpu.exceptions import GetTimeoutError

        if self.store.is_ready(object_id) or \
                self.store.has_local_producer(object_id) or \
                self.scheduler.lineage_for(object_id.task_id()) is not None:
            return  # locally produced: the plain store wait covers it
        # An EXPLICIT caller timeout is the contract — longer or shorter
        # than the default wait bound; dep_wait_s only bounds the
        # unbounded (timeout=None) case.
        bound = float(GlobalConfig.dep_wait_s) if timeout is None \
            else float(timeout)
        deadline = _time.monotonic() + bound
        owner = self.borrowed_owner(object_id.binary())
        if owner is not None and self.owner_resolver is not None:
            self.owner_resolver.resolve(
                object_id.binary(), owner[1], owner[0], deadline=deadline)
            return
        # Owner unknown: head fallback directory. Subscribe BEFORE the
        # first pull so an announce landing in between still wakes us.
        import queue as _queue

        sub = None
        try:
            try:
                sub = self.head_client.subscribe(
                    "obj|" + object_id.binary().hex())
            except Exception:  # noqa: BLE001 — head hiccup: the bounded
                sub = None     # store waits below degrade gracefully
            while True:
                self._maybe_pull_from_head(object_id)
                if self.store.is_ready(object_id) or \
                        self.store.has_local_producer(object_id):
                    return
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    raise GetTimeoutError(
                        f"foreign object {object_id.hex()[:16]}… was "
                        f"never announced/resolvable within "
                        f"{bound:.0f}s (RAY_TPU_DEP_WAIT_S)")
                if sub is not None:
                    try:
                        sub.get(timeout=min(remaining, 5.0))
                    except _queue.Empty:
                        pass  # deadline re-check; no announce yet
                else:
                    self.store.wait([object_id], 1, min(remaining, 0.25))
        finally:
            if sub is not None:
                try:
                    sub.close()
                except Exception:  # noqa: BLE001 — head gone
                    pass

    def get_object(self, ref: ObjectRef, timeout: Optional[float] = None):
        router = self.remote_router
        if router is not None and not self.store.is_ready(ref.object_id) \
                and router.handles(ref.object_id):
            router.ensure_local(ref.object_id, timeout=timeout)
        elif self.head_client is not None:
            self._pull_wait(ref.object_id, timeout)
        if self.store.is_lost(ref.object_id):
            # Lineage reconstruction (cluster mode): re-execute producers.
            cluster = getattr(self, "cluster", None)
            if cluster is not None and cluster.recover_object(ref.object_id):
                self.store.clear_lost(ref.object_id)
            else:
                from ray_tpu.exceptions import ObjectLostError

                raise ObjectLostError(
                    f"object {ref.object_id.hex()[:16]}… lost and no "
                    f"lineage is available to reconstruct it")
        serialized = self.store.get(ref.object_id, timeout=timeout)
        value = self.serialization_context.deserialize(serialized)
        if isinstance(value, RayTaskError):
            raise value.as_instanceof_cause()
        return value

    def submit_task(self, spec: TaskSpec) -> List[ObjectRef]:
        # Pin args that are refs for the duration of the task (submitted-refs
        # in the reference's refcount protocol).
        from ray_tpu._private.scheduler import _collect_refs

        if tracing._TRACER is not None and spec.trace is None:
            # Capture the submitting thread's ambient context: local
            # execution bridges spans off task events; routed execution
            # ships it inside the task payload.
            spec.trace = tracing.inject()
            if spec.trace is not None:
                tracing.register_task(spec.task_id.binary(), spec.trace)

        dep_refs = _collect_refs(spec.args, spec.kwargs)
        for ref in dep_refs:
            self.store.add_submitted_ref(ref.object_id)
        cluster = getattr(self, "cluster", None)
        routed = (cluster is None and self.remote_router is not None
                  and self.remote_router.maybe_route(spec))
        if not routed and getattr(self, "client_mode", False):
            # Thin clients never execute locally — zero-resource tasks
            # included; an unroutable task fails loudly instead of
            # queueing against capacity that will never exist here.
            for ref in dep_refs:  # undo the submitted-ref pins
                self.store.remove_submitted_ref(ref.object_id)
            raise RayTpuError(
                "client-mode driver (ray://) has no local execution "
                "capacity and no feasible cluster node accepted the task "
                "— start node daemons with `ray-tpu start --address=`")
        if not routed:
            # Remote results have no local producer — their bytes arrive
            # by head-relayed pull, which a producer mark would suppress.
            for oid in spec.return_ids:
                self.store.mark_local_producer(oid)
        refs = [ObjectRef(oid) for oid in spec.return_ids]
        if dep_refs:
            def _release(_refs=dep_refs):
                for r in _refs:
                    self.store.remove_submitted_ref(r.object_id)
            self.store.on_ready(spec.return_ids[0], _release)
        if routed:
            pass  # the router owns dispatch + completion
        elif cluster is not None:
            cluster.submit(spec)
        else:
            self.scheduler.submit(spec)
        return refs

    def wait(self, object_ids: List[ObjectID], num_returns: int,
             timeout: Optional[float]):
        router = self.remote_router
        if router is not None:
            # Completed-but-unpulled remote results count as ready only
            # once local; fetch them in the background so wait() observes
            # completion promptly.
            for oid in object_ids:
                if router.handles(oid) and not self.store.is_ready(oid):
                    router.prefetch(oid)
        if self.head_client is not None:
            for oid in object_ids:
                if self.store.is_ready(oid):
                    continue
                owner = self.borrowed_owner(oid.binary())
                if owner is not None and self.owner_resolver is not None:
                    # Borrowed ref: resolve through its OWNER in the
                    # background (deduped) — the head's directory never
                    # saw this object.
                    self.owner_resolver.prefetch(oid.binary(), owner)
                else:
                    self._maybe_pull_from_head(oid)
        return self.store.wait(object_ids, num_returns, timeout)

    # -------------------------------------------------------- internal KV ---
    # With a head attached the KV is cluster-global (GCS-KV semantics);
    # standalone it is driver-local.
    def kv_put(self, key: bytes, value: bytes, overwrite: bool = True) -> bool:
        if self.head_client is not None:
            return self.head_client.kv_put(key, value, overwrite)
        with self._kv_lock:
            if not overwrite and key in self._kv:
                return False
            self._kv[key] = value
            return True

    def kv_get(self, key: bytes) -> Optional[bytes]:
        if self.head_client is not None:
            return self.head_client.kv_get(key)
        with self._kv_lock:
            return self._kv.get(key)

    def kv_del(self, key: bytes) -> bool:
        if self.head_client is not None:
            return self.head_client.kv_del(key)
        with self._kv_lock:
            return self._kv.pop(key, None) is not None

    def kv_keys(self, prefix: bytes = b"") -> List[bytes]:
        if self.head_client is not None:
            return self.head_client.kv_keys(prefix)
        with self._kv_lock:
            return [k for k in self._kv if k.startswith(prefix)]

    def shutdown(self):
        self.is_alive = False
        actors = list(self.actors.values())
        for actor in actors:
            if getattr(actor, "borrower", False):
                continue  # not ours to kill: the owning driver decides
            try:
                actor.terminate(no_restart=True)
            except Exception:  # noqa: BLE001
                pass
        for actor in actors:
            # Join the loop threads BEFORE the shm store unmaps: a
            # process-actor loop tears its channels down on _TERMINATE and
            # must not race the munmap.
            try:
                actor.join(timeout=2)
            except Exception:  # noqa: BLE001
                pass
        self.actors.clear()
        self.named_actors.clear()
        if self.memory_monitor is not None:
            self.memory_monitor.stop()
            self.memory_monitor = None
        if self.sanitizer_watchdog is not None:
            self.sanitizer_watchdog.stop()
            self.sanitizer_watchdog = None
        self.scheduler.shutdown()
        if self.worker_pool is not None:
            self.worker_pool.shutdown()
            self.worker_pool = None
        if getattr(self, "log_monitor", None) is not None:
            self.log_monitor.stop()
            self.log_monitor = None
        if self.remote_router is not None:
            self.remote_router.shutdown()
            self.remote_router = None
        if self.head_client is not None:
            self.head_client.close()
            self.head_client = None
        if self.shm_store is not None:
            self.shm_store.close()
            self.shm_store = None


_global_worker: Optional[Worker] = None
_init_lock = threading.Lock()


def _try_global_worker() -> Optional[Worker]:
    return _global_worker


def try_live_worker() -> Optional[Worker]:
    """The global worker iff one is up AND alive — the runtime-discovery
    check the KV-backed planes (memory:// filesystem, workflow journal)
    share."""
    w = _global_worker
    return w if w is not None and w.is_alive else None


def global_worker() -> Worker:
    if _global_worker is None:
        raise RayTpuError(
            "ray_tpu has not been initialized; call ray_tpu.init() first "
            "(or use auto-init by calling a remote function)."
        )
    return _global_worker


def is_initialized() -> bool:
    return _global_worker is not None


def init(num_cpus: Optional[int] = None, num_tpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         _system_config: Optional[Dict[str, Any]] = None,
         ignore_reinit_error: bool = False, namespace: str = "default",
         worker_mode: Optional[str] = None,
         address: Optional[str] = None,
         **_ignored) -> "Worker":
    global _global_worker
    with _init_lock:
        if _global_worker is not None:
            if ignore_reinit_error:
                return _global_worker
            raise RayTpuError(
                "ray_tpu.init() called twice; pass ignore_reinit_error=True "
                "to allow.")
        if _system_config:
            GlobalConfig.apply_system_config(_system_config)
        if address in ("auto", "local"):
            from ray_tpu._private.head_service import DEFAULT_PORT

            address = f"127.0.0.1:{DEFAULT_PORT}"
        client_mode = bool(address) and address.startswith("ray://")
        if client_mode:
            # Ray-Client role: a THIN attach — this process keeps no task
            # execution capacity (num_cpus=0, no process pool); every
            # .remote() routes onto the cluster's node daemons through
            # the head, and results pull back on demand. Actors created
            # here still live in this process (cross-driver named actors
            # resolve cluster-wide as usual).
            address = address[len("ray://"):]
            num_cpus = 0
            num_tpus = 0
            resources = {}
            worker_mode = worker_mode or "thread"
        _global_worker = Worker(num_cpus=num_cpus, num_tpus=num_tpus,
                                resources=resources,
                                worker_mode=worker_mode,
                                head_address=address)
        _global_worker.client_mode = client_mode
        _global_worker.namespace = namespace
        atexit.register(shutdown)
        return _global_worker


def shutdown():
    global _global_worker
    with _init_lock:
        if _global_worker is None:
            return
        _global_worker.shutdown()
        _global_worker = None


def auto_init() -> Worker:
    if _global_worker is None:
        init(ignore_reinit_error=True)
    return _global_worker


# ------------------------------------------------------------ public verbs --
def put(value: Any) -> ObjectRef:
    return auto_init().put_object(value)


def get(refs: Union[ObjectRef, List[ObjectRef]],
        *, timeout: Optional[float] = None):
    worker = auto_init()
    if isinstance(refs, ObjectRef):
        return worker.get_object(refs, timeout=timeout)
    if not isinstance(refs, list):
        raise TypeError(
            f"get() expects an ObjectRef or list of ObjectRefs, got "
            f"{type(refs)}")
    # Pipelined result prefetch: kick off background pulls for every
    # remote-routed ref up front so the sequential get loop below finds
    # most bytes already local instead of paying one pull RTT per ref.
    router = worker.remote_router
    if router is not None:
        for r in refs:
            if worker.store.is_ready(r.object_id):
                continue
            if router.handles(r.object_id):
                router.prefetch(r.object_id)
            else:
                owner = worker.borrowed_owner(r.object_id.binary())
                if owner is not None and \
                        worker.owner_resolver is not None:
                    worker.owner_resolver.prefetch(
                        r.object_id.binary(), owner)
    # One overall deadline across the whole list, not per ref.
    import time as _time

    deadline = None if timeout is None else _time.monotonic() + timeout
    out = []
    for r in refs:
        remaining = None
        if deadline is not None:
            remaining = max(deadline - _time.monotonic(), 0.0)
        out.append(worker.get_object(r, timeout=remaining))
    return out


def wait(refs: List[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    worker = auto_init()
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    if len(set(refs)) != len(refs):
        raise ValueError("wait() expects a list of unique ObjectRefs")
    if num_returns > len(refs):
        raise ValueError(
            f"num_returns ({num_returns}) exceeds number of refs "
            f"({len(refs)})")
    ready_ids, not_ready_ids = worker.wait(
        [r.object_id for r in refs], num_returns, timeout)
    by_id = {r.object_id: r for r in refs}
    return ([by_id[i] for i in ready_ids], [by_id[i] for i in not_ready_ids])


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    worker = global_worker()
    task_id = ref.object_id.task_id()
    removed = worker.scheduler.cancel(task_id, force=force)
    if removed or force:
        worker.store.cancel(ref.object_id, task_id)
