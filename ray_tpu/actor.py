"""Actors: stateful workers with ordered method execution.

Rebuild of the reference's actor surface (reference: python/ray/actor.py and
the ActorTaskSubmitter/TaskReceiver ordering machinery [unverified]).
``@remote`` on a class yields an ActorClass; ``.remote()`` creates an actor
backed by a dedicated execution loop (one thread for sync actors, an asyncio
event loop for async actors, a thread pool for ``max_concurrency > 1``);
method calls are submitted in order per caller and return ObjectRefs.
``max_restarts`` restarts a killed actor with fresh state; named actors are
resolvable via ``get_actor``.
"""

from __future__ import annotations

import asyncio
import inspect
import queue
import threading
from typing import Any, Dict, Optional

from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ActorID, ObjectID, TaskID
from ray_tpu._private.log import get_logger
from ray_tpu._private.tpu_chips import chips_requested
from ray_tpu._private.worker import ObjectRef, auto_init, global_worker
from ray_tpu._private import tracing

from ray_tpu.exceptions import (
    ActorDiedError,
    RayActorError,
    RayTaskError,
    TaskCancelledError,
)

log = get_logger(__name__)

_TERMINATE = object()


class _ClosureCall:
    """A raw closure run on the actor's execution loop with the instance —
    used by compiled DAGs to host their long-running exec loop inside the
    actor (serialized with normal method calls, do_exec_tasks parity)."""

    __slots__ = ("fn",)

    def __init__(self, fn):
        self.fn = fn


class _MethodCall:
    __slots__ = ("method_name", "args", "kwargs", "return_ids", "name",
                 "cancelled", "streaming", "backpressure")

    def __init__(self, method_name, args, kwargs, return_ids, name,
                 streaming: bool = False, backpressure: int = 0):
        self.method_name = method_name
        self.args = args
        self.kwargs = kwargs
        self.return_ids = return_ids
        self.name = name
        self.cancelled = False
        # Generator method (num_returns="streaming"): return_ids holds
        # only the stream END MARKER; items commit per yield.
        self.streaming = streaming
        self.backpressure = backpressure


class _ActorRuntime:
    """Execution loop + mailbox for one actor instance."""

    def __init__(self, actor_id: ActorID, cls: type, init_args, init_kwargs,
                 *, max_concurrency: int, max_restarts: int, name: str,
                 actor_name: Optional[str],
                 runtime_target: Optional[str] = None,
                 num_tpus: int = 0):
        self.actor_id = actor_id
        self.cls = cls
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.max_restarts = max_restarts
        self.restarts_used = 0
        self.class_name = name
        self.actor_name = actor_name
        self._chips: tuple = ()         # TPU chips held (see below)
        self._chips_lock = threading.Lock()
        self.dead = False
        self.death_cause: Optional[str] = None
        self._mailbox: "queue.Queue" = queue.Queue()
        self._seq_counter = 0
        self._lock = threading.Lock()
        self.is_async = any(
            inspect.iscoroutinefunction(m) or inspect.isasyncgenfunction(m)
            for _, m in inspect.getmembers(cls, inspect.isfunction)
        )
        # Default concurrency: async actors interleave up to 1000 coroutines
        # (reference default); sync actors are single-threaded unless asked.
        if max_concurrency is None:
            max_concurrency = 1000 if self.is_async else 1
        self.max_concurrency = max(int(max_concurrency), 1)
        # Process plane: EVERY actor flavor lives in a dedicated worker
        # process (reference model: every actor is a worker process), so an
        # actor segfault/kill -9 never touches the driver. Sync
        # single-threaded actors use the simple request/reply channel;
        # async and multi-threaded actors use the multiplexed submit/
        # calldone protocol (out-of-order completions over the same
        # channels). ``runtime="driver"`` opts back into the in-driver
        # loop explicitly (e.g. actors that must share driver memory).
        worker = global_worker()
        self.runtime_target = runtime_target
        self.use_process = (
            getattr(worker, "shm_store", None) is not None
            and runtime_target != "driver")
        self.use_mux = self.use_process and (
            self.is_async or self.max_concurrency > 1)
        self._proc = None
        self._restart_pending = False
        self.pid: Optional[int] = None
        # Device ownership: an actor that declares TPU holds whole chips
        # for its lifetime (across restarts) and its worker process is
        # the one process that opens them. A claim that cannot be met
        # fails here, naming the holders — it neither waits nor runs on
        # the CPU instead.
        self._chip_pool = worker
        if num_tpus:
            holder = f"actor {name} (id {actor_id.hex()[:12]})"
            if not worker.resource_pool.try_acquire(
                    {"TPU": float(num_tpus)}):
                raise worker.chips.busy(num_tpus, holder)
            self._chips = worker.chips.take(num_tpus, holder)
        self._start_loop()

    @property
    def dead(self) -> bool:
        return self._dead

    @dead.setter
    def dead(self, value: bool):
        self._dead = value
        if value:
            self._release_chips()

    def _release_chips(self):
        """A dead actor runs nothing more: make sure the process that
        opened its chips is gone, then hand them to the next claimant."""
        with self._chips_lock:
            chips, self._chips = self._chips, ()
        if not chips:
            return
        proc = self._proc
        if proc is not None:
            proc.kill()
            try:
                # Seconds, for a process that mapped several chips.
                proc.proc.wait(timeout=30.0)
            except Exception as exc:  # noqa: BLE001 — still release
                log.warning("actor %s worker %s did not exit after kill: "
                            "%r", self.class_name, proc.pid, exc)
        self._chip_pool.chips.give_back(chips)
        self._chip_pool.resource_pool.release({"TPU": float(len(chips))})

    # ---------------------------------------------------------------- loops
    def _start_loop(self):
        self._instance_ready = threading.Event()
        self._init_error: Optional[BaseException] = None
        mailbox = self._mailbox
        if self.use_process:
            target = self._run_proc_mux if self.use_mux else self._run_proc
        else:
            target = self._run_async if self.is_async else self._run_sync
        self._thread = threading.Thread(
            target=target, args=(mailbox,),
            daemon=True, name=f"actor-{self.class_name}",
        )
        self._thread.start()

    def _construct(self):
        try:
            self.instance = self.cls(*self.init_args, **self.init_kwargs)
            self._init_error = None
        except BaseException as e:  # noqa: BLE001 — init error boundary
            self._init_error = e
            self.dead = True
            self.death_cause = f"__init__ failed: {e!r}"
        finally:
            self._instance_ready.set()

    def _run_sync(self, mailbox):
        self._construct()
        worker = global_worker()
        if self._init_error is not None:
            self._drain_with_error(mailbox)
            return
        if self.max_concurrency > 1:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(max_workers=self.max_concurrency)
            while True:
                call = mailbox.get()
                if call is _TERMINATE:
                    pool.shutdown(wait=False)
                    return
                if isinstance(call, _ClosureCall):
                    pool.submit(call.fn, self.instance)
                else:
                    pool.submit(self._execute_call, worker, call)
        else:
            while True:
                call = mailbox.get()
                if call is _TERMINATE:
                    return
                if isinstance(call, _ClosureCall):
                    call.fn(self.instance)
                else:
                    self._execute_call(worker, call)

    def _run_async(self, mailbox):
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._construct()
        worker = global_worker()
        if self._init_error is not None:
            self._drain_with_error(mailbox)
            return

        async def _main():
            sem = asyncio.Semaphore(self.max_concurrency)
            while True:
                call = await loop.run_in_executor(None, mailbox.get)
                if call is _TERMINATE:
                    return
                if isinstance(call, _ClosureCall):
                    # Blocking exec loop: keep it off the event loop so the
                    # async actor's coroutines stay responsive (async actors
                    # interleave by contract, so no serialization promise is
                    # broken here).
                    loop.run_in_executor(None, call.fn, self.instance)
                    continue
                await sem.acquire()

                async def _run(call=call):
                    try:
                        await self._execute_call_async(worker, call)
                    finally:
                        sem.release()

                loop.create_task(_run())

        loop.run_until_complete(_main())
        loop.close()

    # ------------------------------------------------- process-backed actor
    def _spawn_proc(self):
        """Spawn the dedicated worker process and construct the instance in
        it (fresh state). Raises on construction failure."""
        import cloudpickle

        from ray_tpu._private.worker_pool import (
            WorkerProcess,
            maybe_stage,
            pack_args,
        )

        import os

        worker = global_worker()
        proc = WorkerProcess(worker.shm_store,
                             max_msg=GlobalConfig.worker_channel_bytes,
                             log_dir=os.path.join(worker.session_dir,
                                                  "logs"),
                             tpu_chips=self._chips,
                             host_chips=worker.chips.total)
        staged = []
        try:
            args, kwargs = _resolve_values(
                worker, self.init_args, self.init_kwargs)
            payload, staged = pack_args(
                worker.shm_store, worker.serialization_context, args, kwargs)
            limit = max(proc.max_msg // 4, 64 * 1024)
            cls_bytes, st = maybe_stage(
                worker.shm_store, cloudpickle.dumps(self.cls), limit)
            staged += st
            payload, st = maybe_stage(worker.shm_store, payload, limit)
            staged += st
            if self.use_mux:
                mode = "async" if self.is_async else "threaded"
                proc.request(("actor_new2", cls_bytes, payload, mode,
                              self.max_concurrency))
            else:
                proc.request(("actor_new", cls_bytes, payload))
        except BaseException:
            proc.shutdown(timeout=0.1)
            raise
        finally:
            for key in staged:
                try:
                    worker.shm_store.delete(key)
                except Exception:  # noqa: BLE001
                    pass
        return proc

    def _run_proc(self, mailbox):
        worker = global_worker()
        try:
            self._proc = self._spawn_proc()
            self.pid = self._proc.pid
            self._init_error = None
        except BaseException as e:  # noqa: BLE001 — init error boundary
            self._init_error = e
            self.dead = True
            self.death_cause = f"__init__ failed: {e!r}"
            self._instance_ready.set()
            self._drain_with_error(mailbox)
            return
        # DAG exec loops see a proxy whose method calls RPC into the worker
        # process on this thread — same serialization contract as in-driver
        # actors.
        self.instance = _ProcessActorProxy(self)
        self._instance_ready.set()
        while True:
            call = mailbox.get()
            if call is _TERMINATE:
                if self._proc is not None:
                    self._proc.shutdown(timeout=0.5)
                return
            if isinstance(call, _ClosureCall):
                try:
                    call.fn(self.instance)
                except Exception as exc:  # exec loop boundary
                    log.warning("actor closure call failed; exec loop "
                                "continues: %r", exc)
                continue
            if self._restart_pending and not self.dead:
                try:
                    self._proc.shutdown(timeout=0.1)
                    self._proc = self._spawn_proc()
                    self.pid = self._proc.pid
                except BaseException as e:  # noqa: BLE001
                    self.dead = True
                    self.death_cause = f"restart failed: {e!r}"
                finally:
                    self._restart_pending = False
            if self.dead:
                self._fail_call(worker, call, ActorDiedError(
                    self.actor_id, self.death_cause or "actor is dead"))
                continue
            self._execute_call_proc(worker, call)

    # ------------------------------------ concurrent process-backed actor
    def _run_proc_mux(self, mailbox):
        """Mailbox loop for async/threaded actors in a worker process:
        calls are fire-and-forget 'actor_submit' writes; a pump thread
        matches out-of-order ('calldone', call_id, …) completions, so up
        to max_concurrency calls overlap inside the worker while this
        loop keeps dispatching (reference: every actor is a worker
        process, including asyncio and threaded actors — SURVEY §3.3)."""
        worker = global_worker()
        try:
            self._proc = self._spawn_proc()
            self.pid = self._proc.pid
            self._init_error = None
        except BaseException as e:  # noqa: BLE001 — init error boundary
            self._init_error = e
            self.dead = True
            self.death_cause = f"__init__ failed: {e!r}"
            self._instance_ready.set()
            self._drain_with_error(mailbox)
            return
        self.instance = _ProcessActorProxy(self)
        self._mux_pending: Dict[int, dict] = {}
        self._mux_lock = threading.Lock()
        self._mux_call_counter = 0
        self._start_pump(worker)
        self._instance_ready.set()
        while True:
            call = mailbox.get()
            if call is _TERMINATE:
                if self._proc is not None:
                    self._proc.shutdown(timeout=0.5)
                return
            if isinstance(call, _ClosureCall):
                try:
                    call.fn(self.instance)
                except Exception as exc:  # exec loop boundary
                    log.warning("actor closure call failed; exec loop "
                                "continues: %r", exc)
                continue
            if (self._restart_pending or not self._proc.alive()) \
                    and not self.dead:
                self._mux_respawn(worker)
            if self.dead:
                self._fail_call(worker, call, ActorDiedError(
                    self.actor_id, self.death_cause or "actor is dead"))
                continue
            self._mux_dispatch(worker, call)

    def _mux_respawn(self, worker):
        """Replace a dead/killed worker process with a fresh one (fresh
        actor state), consuming restart budget unless terminate() already
        counted it."""
        if self._restart_pending:
            consume = False
        elif self.restarts_used < self.max_restarts:
            consume = True
        else:
            self.dead = True
            self.death_cause = (self.death_cause
                                or "actor worker process died")
            return
        self._restart_pending = False
        if consume:
            self.restarts_used += 1
        # Drain in-flight calls against the dead process FIRST: the old
        # pump may exit via its proc-identity check without failing them,
        # and nothing else ever would (hang). Waiters are notified too —
        # their liveness probe watches the captured (dead) proc, not the
        # healthy replacement.
        with self._mux_lock:
            pending, self._mux_pending = dict(self._mux_pending), {}
        err = ActorDiedError(
            self.actor_id, self.death_cause or "actor worker process died")
        for entry in pending.values():
            if "waiter" in entry:
                entry["status"] = "died"
                entry["waiter"].set()
                continue
            self._fail_call(worker, entry["call"], err)
            for key in entry["staged"]:
                try:
                    worker.shm_store.delete(key)
                except Exception:  # noqa: BLE001
                    pass
        try:
            self._proc.shutdown(timeout=0.1)
            self._proc = self._spawn_proc()
            self.pid = self._proc.pid
            self._start_pump(worker)
        except BaseException as e:  # noqa: BLE001
            self.dead = True
            self.death_cause = f"restart failed: {e!r}"

    def _mux_dispatch(self, worker, call: _MethodCall):
        from ray_tpu._private.worker_pool import (
            maybe_stage,
            oid_key,
            pack_args,
        )

        if call.cancelled:
            self._fail_call(worker, call, TaskCancelledError())
            return
        shm = worker.shm_store
        task_id = call.return_ids[0].task_id()
        worker.task_events.record(task_id, "RUNNING", name=call.name)
        staged: list = []
        ret_keys = [oid_key(oid) for oid in call.return_ids]
        call_id = None
        try:
            args, kwargs = _resolve_actor_args(worker, call)
            payload, staged = pack_args(
                shm, worker.serialization_context, args, kwargs)
            payload, st = maybe_stage(
                shm, payload, max(self._proc.max_msg // 4, 64 * 1024))
            staged += st
            for key in ret_keys:
                try:
                    shm.delete(key)
                except Exception as exc:  # slot already free
                    log.debug("stale ret-key %s delete: %r", key, exc)
            entry = {"call": call, "staged": staged, "ret_keys": ret_keys}
            stream_budget = None
            if call.streaming:
                # Item frames come back multiplexed as
                # ("calldone", call_id, "item", ...); consumption acks go
                # out as fire-and-forget stream_ack requests — the worker
                # main loop drains the req channel continuously, so no
                # dedicated ack channel is needed on the mux plane.
                stream_budget = int(call.backpressure)
                stream = worker.streams.get_or_create(task_id)
                entry["stream"] = stream
                entry["cancel_sent"] = False
                proc = self._proc
                tid_bin = task_id.binary()

                def _wire_ack(n, _p=proc, _t=tid_bin, _e=entry):
                    try:
                        _p._req.write(("stream_ack", _t, int(n)),
                                      timeout=5.0)
                        if n > _e.get("acked", 0):
                            _e["acked"] = n
                    except Exception:  # noqa: BLE001 — dropped ack: the
                        pass           # pump's watermark re-send retries
                stream.add_consume_listener(_wire_ack)
                entry["wire_ack"] = _wire_ack
            with self._mux_lock:
                self._mux_call_counter += 1
                call_id = self._mux_call_counter
                self._mux_pending[call_id] = entry
            self._proc._req.write(
                ("actor_submit", call_id, call.method_name, payload,
                 ret_keys, len(call.return_ids), task_id.binary(),
                 call.name, stream_budget), timeout=60.0)
        except BaseException as exc:  # noqa: BLE001 — dispatch boundary
            with self._mux_lock:
                if call_id is not None:
                    self._mux_pending.pop(call_id, None)
            for key in staged:
                try:
                    shm.delete(key)
                except Exception as del_exc:  # slot already free
                    log.debug("staged-arg key %s delete: %r", key,
                              del_exc)
            if isinstance(exc, RayTaskError):
                self._fail_call(worker, call, exc)
            else:
                self._fail_call(
                    worker, call, RayTaskError.from_exception(call.name, exc))
            worker.task_events.record(task_id, "FAILED", name=call.name)

    def _start_pump(self, worker):
        self._pump_thread = threading.Thread(
            target=self._pump_loop, args=(worker, self._proc), daemon=True,
            name=f"actor-pump-{self.class_name}")
        self._pump_thread.start()

    def _pump_loop(self, worker, proc):
        """Read out-of-order completions off the reply channel; on worker
        death fail every in-flight call with ActorDiedError (the
        interrupted calls are NOT retried — reference restart
        semantics)."""
        import pickle as _pickle

        from ray_tpu._private.serialization import SerializedObject
        from ray_tpu.exceptions import ChannelError, ChannelTimeoutError

        from ray_tpu._private.streaming import stream_end_id, stream_item_id

        shm = worker.shm_store
        while True:
            try:
                msg = proc._rep.read(timeout=0.2)
            except ChannelTimeoutError:
                if not proc.alive() or proc is not self._proc:
                    break
                self._mux_propagate_cancels(proc)
                self._mux_resend_watermarks(proc)
                continue
            except (ChannelError, Exception) as exc:  # noqa: BLE001
                log.debug("mux reply channel torn down; pump exiting: "
                          "%r", exc)
                break
            if not msg or msg[0] != "calldone":
                continue
            _, call_id, status, value = msg
            if status == "item":
                # Mid-stream yield: commit the item WITHOUT popping the
                # pending entry (the stream is still in flight).
                with self._mux_lock:
                    entry = self._mux_pending.get(call_id)
                stream = (entry or {}).get("stream")
                if stream is None:
                    continue  # stale frame from a replaced worker
                try:
                    idx, field = value
                    if isinstance(field, (tuple, list)) and field and \
                            field[0] == "shm":
                        raw = bytes(shm.get(field[1]))
                        try:
                            shm.delete(field[1])
                        except Exception as del_exc:  # raced away
                            log.debug("staged item %s delete: %r",
                                      field[1], del_exc)
                    else:
                        raw = bytes(field)
                    tid = entry["call"].return_ids[0].task_id()
                    worker.store.put(stream_item_id(tid, int(idx)),
                                     SerializedObject.from_bytes(raw))
                    stream.commit(int(idx))
                except Exception as exc:  # item frame corrupt: the
                    # terminal frame settles the call
                    log.warning("dropping corrupt stream item frame: "
                                "%r", exc)
                self._mux_propagate_cancels(proc)
                continue
            with self._mux_lock:
                entry = self._mux_pending.pop(call_id, None)
            if entry is None:
                continue
            if "waiter" in entry:  # proxy apply: hand over and notify
                entry["status"], entry["value"] = status, value
                entry["waiter"].set()
                continue
            call = entry["call"]
            try:
                if status == "ok":
                    for oid, key in zip(call.return_ids,
                                        entry["ret_keys"]):
                        raw = bytes(shm.get(key))
                        worker.store.put(
                            oid, SerializedObject.from_bytes(raw))
                        shm.delete(key)
                    worker.task_events.record(
                        call.return_ids[0].task_id(), "FINISHED",
                        name=call.name)
                elif status == "ok_stream":
                    tid = call.return_ids[0].task_id()
                    total = int(value)
                    worker.store.put(
                        stream_end_id(tid),
                        worker.serialization_context.serialize(total))
                    entry["stream"].finish(total)
                    worker.task_events.record(tid, "FINISHED",
                                              name=call.name)
                elif status == "cancelled":
                    self._fail_call(worker, call, TaskCancelledError(
                        call.return_ids[0].task_id()))
                elif status == "err":
                    self._fail_call(worker, call, _pickle.loads(value))
                    worker.task_events.record(
                        call.return_ids[0].task_id(), "FAILED",
                        name=call.name)
                else:  # okv/okshm belong to proxy waiters; shouldn't hit
                    self._fail_call(worker, call, RayActorError(
                        self.actor_id, f"unexpected status {status!r}"))
            except Exception as exc:  # noqa: BLE001 — completion boundary
                self._fail_call(
                    worker, call,
                    RayTaskError.from_exception(call.name, exc))
            finally:
                for key in entry["staged"]:
                    try:
                        shm.delete(key)
                    except Exception as del_exc:  # slot already free
                        log.debug("settled-call staged key %s delete: "
                                  "%r", key, del_exc)
        # Worker died (or was replaced): fail everything still in flight
        # against THIS process.
        if proc is not self._proc:
            return
        with self._mux_lock:
            pending, self._mux_pending = dict(self._mux_pending), {}
        err = ActorDiedError(
            self.actor_id,
            self.death_cause or "actor worker process died")
        for entry in pending.values():
            if "waiter" in entry:
                entry["status"] = "died"
                entry["waiter"].set()
                continue
            self._fail_call(worker, entry["call"], err)
            for key in entry["staged"]:
                try:
                    shm.delete(key)
                except Exception as del_exc:  # slot already free
                    log.debug("dead-actor staged key %s delete: %r",
                              key, del_exc)

    def _mux_propagate_cancels(self, proc):
        """A consumer dropped its generator mid-stream: signal the worker
        (once per call) so its yield loop stops between yields."""
        with self._mux_lock:
            entries = [e for e in self._mux_pending.values()
                       if e.get("stream") is not None
                       and e["stream"].cancelled
                       and not e.get("cancel_sent")]
            for e in entries:
                e["cancel_sent"] = True
        for e in entries:
            try:
                proc._req.write(
                    ("stream_ack",
                     e["call"].return_ids[0].task_id().binary(), -1),
                    timeout=1.0)
            except Exception:  # noqa: BLE001 — worker died: pump exits
                pass

    def _mux_resend_watermarks(self, proc):
        """Ack-loss recovery: _wire_ack is fire-and-forget, so a single
        timed-out write would otherwise park a backpressured stream
        forever (producer waits for a watermark that never arrives). On
        pump-idle slices, re-send any consumption watermark ahead of the
        last delivered one."""
        with self._mux_lock:
            stale = [(e, e["stream"].consumed)
                     for e in self._mux_pending.values()
                     if e.get("stream") is not None
                     and not e["stream"].cancelled
                     and e["stream"].consumed > e.get("acked", 0)]
        for e, n in stale:
            try:
                proc._req.write(
                    ("stream_ack",
                     e["call"].return_ids[0].task_id().binary(), int(n)),
                    timeout=1.0)
                if n > e.get("acked", 0):
                    e["acked"] = n
            except Exception:  # noqa: BLE001 — retried next idle slice
                pass

    def _execute_call_proc(self, worker, call: _MethodCall):
        from ray_tpu._private.serialization import SerializedObject
        from ray_tpu._private.worker_pool import (
            maybe_stage,
            oid_key,
            pack_args,
        )
        from ray_tpu.exceptions import WorkerCrashedError

        if call.cancelled:
            self._fail_call(worker, call, TaskCancelledError())
            return
        shm = worker.shm_store
        task_id = call.return_ids[0].task_id()
        worker.task_events.record(task_id, "RUNNING", name=call.name)
        staged: list = []
        ret_keys = [oid_key(oid) for oid in call.return_ids]
        try:
            args, kwargs = _resolve_actor_args(worker, call)
            payload, staged = pack_args(
                shm, worker.serialization_context, args, kwargs)
            payload, st = maybe_stage(
                shm, payload, max(self._proc.max_msg // 4, 64 * 1024))
            staged += st
            if call.streaming:
                # Generator method on a sync process actor: the same
                # item-frame pump as streaming tasks (pause protocol in
                # worker_main, acks on the stream-ack channel).
                from ray_tpu._private.scheduler import pump_stream_replies

                stream = worker.streams.get_or_create(task_id)
                self._proc._req.write(
                    ("actor_stream", call.method_name, payload,
                     task_id.binary(), call.name,
                     int(call.backpressure)), timeout=60.0)
                pump_stream_replies(
                    self._proc, task_id, call.name, stream, worker.store,
                    shm, worker.serialization_context)
                worker.task_events.record(task_id, "FINISHED",
                                          name=call.name)
                return
            for key in ret_keys:  # clear stale keys from a crashed attempt
                try:
                    shm.delete(key)
                except Exception:  # noqa: BLE001
                    pass
            self._proc.request(
                ("actor_call", call.method_name, payload, ret_keys,
                 len(call.return_ids), task_id.binary(), call.name))
            for oid, key in zip(call.return_ids, ret_keys):
                raw = bytes(shm.get(key))
                worker.store.put(oid, SerializedObject.from_bytes(raw))
                shm.delete(key)
            worker.task_events.record(task_id, "FINISHED", name=call.name)
        except WorkerCrashedError as e:
            self._on_proc_crash(worker, call, e)
            worker.task_events.record(task_id, "FAILED", name=call.name)
        except BaseException as exc:  # noqa: BLE001 — method error boundary
            if isinstance(exc, (RayTaskError, TaskCancelledError)):
                self._fail_call(worker, call, exc)
            else:
                self._fail_call(
                    worker, call, RayTaskError.from_exception(call.name, exc))
            worker.task_events.record(task_id, "FAILED", name=call.name)
        finally:
            for key in staged:
                try:
                    shm.delete(key)
                except Exception:  # noqa: BLE001
                    pass

    def _proxy_apply(self, method_name: str, args, kwargs):
        """Synchronous method application for _ProcessActorProxy (runs on
        the actor loop thread; the result rides the reply channel)."""
        from ray_tpu._private.serialization import SerializedObject
        from ray_tpu._private.worker_pool import maybe_stage, pack_args
        from ray_tpu.exceptions import WorkerCrashedError

        worker = global_worker()
        if self.dead or self._proc is None or not self._proc.alive():
            raise ActorDiedError(self.actor_id,
                                 self.death_cause or "actor is dead")
        if self.use_mux:
            return self._proxy_apply_mux(worker, method_name, args, kwargs)
        shm = worker.shm_store
        payload, staged = pack_args(
            shm, worker.serialization_context, args, kwargs)
        payload, st = maybe_stage(
            shm, payload, max(self._proc.max_msg // 4, 64 * 1024))
        staged += st
        try:
            raw = self._proc.request(
                ("actor_call", method_name, payload, [], 1, b"",
                 method_name))
            return worker.serialization_context.deserialize(
                SerializedObject.from_bytes(raw))
        except RayTaskError as e:
            # Surface the original exception type — the DAG stage wraps it
            # exactly once, like the in-driver path.
            raise e.as_instanceof_cause() from None
        except WorkerCrashedError as e:
            self.dead = True
            self.death_cause = f"actor worker process died: {e}"
            raise ActorDiedError(self.actor_id, self.death_cause) from e
        finally:
            for key in staged:
                try:
                    shm.delete(key)
                except Exception:  # noqa: BLE001
                    pass

    def _proxy_apply_mux(self, worker, method_name: str, args, kwargs):
        """Proxy apply over the multiplexed channel: register a waiter the
        pump thread resolves (the pump owns the reply channel, so the
        plain request() path would steal its frames)."""
        import pickle as _pickle

        from ray_tpu._private.serialization import SerializedObject
        from ray_tpu._private.worker_pool import maybe_stage, pack_args

        shm = worker.shm_store
        payload, staged = pack_args(
            shm, worker.serialization_context, args, kwargs)
        payload, st = maybe_stage(
            shm, payload, max(self._proc.max_msg // 4, 64 * 1024))
        staged += st
        entry = {"waiter": threading.Event(), "status": None, "value": None}
        proc = self._proc  # liveness must track the proc we dispatched to
        try:
            with self._mux_lock:
                self._mux_call_counter += 1
                call_id = self._mux_call_counter
                self._mux_pending[call_id] = entry
            proc._req.write(
                ("actor_submit", call_id, method_name, payload, [], 1,
                 b"", method_name), timeout=60.0)
            while not entry["waiter"].wait(timeout=0.5):
                if not proc.alive():
                    with self._mux_lock:
                        self._mux_pending.pop(call_id, None)
                    if entry["status"] is None:
                        entry["status"] = "died"
                    break
            status, value = entry["status"], entry["value"]
            if status == "okv":
                return worker.serialization_context.deserialize(
                    SerializedObject.from_bytes(value))
            if status == "okshm":
                raw = bytes(shm.get(value))
                shm.delete(value)
                return worker.serialization_context.deserialize(
                    SerializedObject.from_bytes(raw))
            if status == "err":
                raise _pickle.loads(value).as_instanceof_cause() from None
            # The worker died mid-call. Do NOT mark the actor dead here:
            # _mux_respawn may already have restarted it within budget —
            # only this interrupted call fails (reference restart
            # semantics: interrupted calls are not retried).
            raise ActorDiedError(
                self.actor_id,
                self.death_cause or "actor worker process died mid-call")
        finally:
            for key in staged:
                try:
                    shm.delete(key)
                except Exception:  # noqa: BLE001
                    pass

    def _on_proc_crash(self, worker, call: _MethodCall, exc: BaseException):
        """The actor's worker died mid-call: fail the in-flight call, then
        restart with fresh state if the policy allows (reference actor
        restart semantics — the interrupted call is NOT retried)."""
        self._fail_call(worker, call, ActorDiedError(
            self.actor_id, f"actor worker process died: {exc}"))
        if self._restart_pending:
            consume = False  # terminate(no_restart=False) already counted it
        elif not self.dead and self.restarts_used < self.max_restarts:
            consume = True
        else:
            self.dead = True
            self.death_cause = (self.death_cause
                                or f"actor worker process died: {exc}")
            return
        self._restart_pending = False
        if consume:
            self.restarts_used += 1
        try:
            self._proc.shutdown(timeout=0.1)
            self._proc = self._spawn_proc()
            self.pid = self._proc.pid
        except BaseException as e:  # noqa: BLE001
            self.dead = True
            self.death_cause = f"restart failed: {e!r}"

    # ------------------------------------------------------------ execution
    def _execute_call(self, worker, call: _MethodCall):
        if call.cancelled:
            self._fail_call(worker, call, TaskCancelledError())
            return
        worker.task_events.record(
            call.return_ids[0].task_id(), "RUNNING", name=call.name)
        try:
            method = getattr(self.instance, call.method_name)
            args, kwargs = _resolve_actor_args(worker, call)
            result = method(*args, **kwargs)
            if call.streaming:
                self._stream_call_outputs(worker, call, result)
            else:
                self._store_outputs(worker, call, result)
            worker.task_events.record(
                call.return_ids[0].task_id(), "FINISHED", name=call.name)
        except BaseException as exc:  # noqa: BLE001 — method error boundary
            self._fail_call(
                worker, call, RayTaskError.from_exception(call.name, exc))
            worker.task_events.record(
                call.return_ids[0].task_id(), "FAILED", name=call.name)

    def _stream_call_outputs(self, worker, call: _MethodCall, result):
        """In-driver generator method: commit one object per yield (the
        consumer's next() unblocks immediately), pausing at the
        backpressure budget; a dropped/closed consumer generator cancels
        the loop between yields."""
        from ray_tpu._private.streaming import stream_end_id, stream_item_id

        task_id = call.return_ids[0].task_id()
        stream = worker.streams.get_or_create(task_id)
        ctx = worker.serialization_context
        idx = 0
        it = iter(result)
        try:
            for item in it:
                if call.cancelled or stream.cancelled:
                    raise TaskCancelledError(task_id)
                worker.store.put(stream_item_id(task_id, idx),
                                 ctx.serialize(item))
                stream.commit(idx)
                idx += 1
                if not stream.wait_capacity(call.backpressure):
                    raise TaskCancelledError(task_id)
        except BaseException as exc:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — generator cleanup
                    pass
            stream.set_error(exc)
            raise
        worker.store.put(stream_end_id(task_id), ctx.serialize(idx))
        stream.finish(idx)

    async def _execute_call_async(self, worker, call: _MethodCall):
        if call.cancelled:
            self._fail_call(worker, call, TaskCancelledError())
            return
        try:
            method = getattr(self.instance, call.method_name)
            args, kwargs = _resolve_actor_args(worker, call)
            result = method(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = await result
            if call.streaming:
                if hasattr(result, "__anext__"):
                    await self._stream_call_outputs_async(
                        worker, call, result)
                else:
                    # Sync generator from an async actor: iterate on the
                    # loop's executor so coroutines stay responsive.
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(
                        None, self._stream_call_outputs, worker, call,
                        result)
                return
            self._store_outputs(worker, call, result)
        except BaseException as exc:  # noqa: BLE001
            self._fail_call(
                worker, call, RayTaskError.from_exception(call.name, exc))

    async def _stream_call_outputs_async(self, worker, call: _MethodCall,
                                         agen):
        """Async-generator flavor: pause points poll the stream state
        without blocking the actor's event loop."""
        from ray_tpu._private.streaming import stream_end_id, stream_item_id

        task_id = call.return_ids[0].task_id()
        stream = worker.streams.get_or_create(task_id)
        ctx = worker.serialization_context
        idx = 0
        try:
            async for item in agen:
                if call.cancelled or stream.cancelled:
                    raise TaskCancelledError(task_id)
                worker.store.put(stream_item_id(task_id, idx),
                                 ctx.serialize(item))
                stream.commit(idx)
                idx += 1
                while call.backpressure and not stream.cancelled and \
                        stream.committed - stream.consumed >= \
                        call.backpressure:
                    await asyncio.sleep(0.01)
        except BaseException as exc:
            stream.set_error(exc)
            raise
        worker.store.put(stream_end_id(task_id), ctx.serialize(idx))
        stream.finish(idx)

    def _store_outputs(self, worker, call: _MethodCall, result):
        ctx = worker.serialization_context
        if len(call.return_ids) == 1:
            outputs = [result]
        else:
            outputs = list(result)
            if len(outputs) != len(call.return_ids):
                raise ValueError(
                    f"method {call.name!r} declared num_returns="
                    f"{len(call.return_ids)} but returned {len(outputs)} "
                    f"values")
        for oid, value in zip(call.return_ids, outputs):
            worker.store.put(oid, ctx.serialize(value))

    def _fail_call(self, worker, call: _MethodCall, error: BaseException):
        for oid in call.return_ids:
            worker.store.put_error(oid, error)

    def _drain_with_error(self, mailbox):
        worker = global_worker()
        err = ActorDiedError(self.actor_id, self.death_cause or "actor died")
        while True:
            try:
                call = mailbox.get(timeout=0.5)
            except queue.Empty:
                if self.dead:
                    return
                continue
            if call is _TERMINATE:
                return
            if isinstance(call, _ClosureCall):
                continue  # compiled-DAG loop: its compile-time check reports
            self._fail_call(worker, call, err)

    # ------------------------------------------------------------ submission
    def submit(self, method_name: str, args, kwargs, num_returns: int,
               name: str):
        worker = global_worker()
        with self._lock:
            self._seq_counter += 1
            task_id = TaskID.for_actor_task(self.actor_id, self._seq_counter)
        return_ids = [
            ObjectID.for_task_return(task_id, i) for i in range(num_returns)
        ]
        return self.submit_prepared(method_name, args, kwargs, return_ids,
                                    name)

    def submit_stream(self, method_name: str, args, kwargs, name: str):
        """Submit a generator method (num_returns="streaming"): returns an
        ObjectRefGenerator whose item refs materialize per yield."""
        from ray_tpu._private.streaming import stream_end_id
        from ray_tpu._private.worker import ObjectRefGenerator

        worker = global_worker()
        with self._lock:
            self._seq_counter += 1
            task_id = TaskID.for_actor_task(self.actor_id, self._seq_counter)
        return_ids = [stream_end_id(task_id)]
        worker.store.mark_local_producer(return_ids[0])
        gen = ObjectRefGenerator(task_id, worker)
        if self.dead:
            err = ActorDiedError(self.actor_id,
                                 self.death_cause or "actor is dead")
            worker.store.put_error(return_ids[0], err)
            return gen
        if tracing._TRACER is not None:
            # Ambient caller context → this call's spans (queue/exec
            # via the task-event bridge on the executing runtime).
            tracing.register_task(task_id.binary(), tracing.inject())
        worker.task_events.record(task_id, "PENDING_ACTOR_TASK", name=name)
        call = _MethodCall(
            method_name, args, kwargs, return_ids, name, streaming=True,
            backpressure=GlobalConfig.generator_backpressure_items)
        with self._lock:
            self._mailbox.put(call)
        return gen

    def submit_prepared(self, method_name: str, args, kwargs,
                        return_ids, name: str):
        """Submit with caller-allocated return ids (the cluster actor
        host uses this: the remote driver minted the ids)."""
        worker = global_worker()
        for oid in return_ids:
            worker.store.mark_local_producer(oid)
        refs = [ObjectRef(oid) for oid in return_ids]
        if self.dead:
            err = ActorDiedError(self.actor_id,
                                 self.death_cause or "actor is dead")
            for oid in return_ids:
                worker.store.put_error(oid, err)
            return refs
        if tracing._TRACER is not None:
            tracing.register_task(return_ids[0].task_id().binary(),
                                  tracing.inject())
        worker.task_events.record(return_ids[0].task_id(),
                                  "PENDING_ACTOR_TASK", name=name)
        call = _MethodCall(method_name, args, kwargs, return_ids, name)
        with self._lock:
            self._mailbox.put(call)
        return refs

    def submit_exec_loop(self, fn):
        """Enqueue a long-running closure (compiled-DAG exec loop); it runs
        on the actor's loop thread with the instance and occupies the actor
        until it returns (teardown)."""
        with self._lock:
            self._mailbox.put(_ClosureCall(fn))

    def start_dag_loop(self, desc_bytes: bytes, teardown_event):
        """Ship a compiled-DAG stage schedule INTO this actor's worker
        process (worker_main "dag_exec"): stages execute worker-resident
        over native shm channels — the driver never touches the
        inter-stage payloads (the NCCL-channel analogue for same-host
        worker processes). The mailbox closure occupies the actor until
        the DAG tears down, matching driver-plane semantics."""
        from ray_tpu._private.worker_pool import maybe_stage

        worker = global_worker()

        def run(_instance):
            staged: list = []
            try:
                limit = max(self._proc.max_msg // 4, 64 * 1024)
                field, staged = maybe_stage(
                    worker.shm_store, desc_bytes, limit)
                if self.use_mux:
                    # The pump owns the reply channel; fire the request
                    # raw and hold the mailbox until teardown.
                    self._proc._req.write(("dag_exec", field),
                                          timeout=60.0)
                    teardown_event.wait()
                else:
                    # Blocks until the worker's DAG loop exits (channels
                    # closed at teardown) — occupation by construction.
                    self._proc.request(("dag_exec", field))
            except Exception as exc:  # noqa: BLE001 — crash boundary
                # A dispatch failure means the worker never started its
                # stage loop: the DAG would hang silently. Record it and
                # shout — the user's next execute() timeout has a cause.
                self._dag_loop_error = exc
                if not teardown_event.is_set():
                    import sys
                    import traceback as _tb

                    print(f"ray_tpu: compiled-DAG loop for actor "
                          f"{self.class_name!r} failed to start: "
                          f"{_tb.format_exc()}", file=sys.stderr,
                          flush=True)
            finally:
                for key in staged:
                    try:
                        worker.shm_store.delete(key)
                    except Exception:  # noqa: BLE001
                        pass

        self.submit_exec_loop(run)

    # ------------------------------------------------------------- lifecycle
    def terminate(self, no_restart: bool = True):
        if self.dead and no_restart:
            return
        with self._lock:
            if not no_restart and self.restarts_used < self.max_restarts:
                self.restarts_used += 1
                if self.use_process:
                    # Kill the worker (interrupting any in-flight call); the
                    # loop respawns a fresh process before the next call.
                    self._restart_pending = True
                    if self._proc is not None:
                        self._proc.kill()
                    return
                # Fresh mailbox for the restarted loop; the old loop drains
                # its own mailbox and exits on the _TERMINATE sentinel.
                old_mailbox = self._mailbox
                self._mailbox = queue.Queue()
                old_mailbox.put(_TERMINATE)
                self._start_loop()  # fresh state
                return
            self.dead = True
            self.death_cause = "killed via ray_tpu.kill()"
            if self.use_process and self._proc is not None:
                self._proc.kill()
            self._mailbox.put(_TERMINATE)
        # Release the cluster-wide name so it can be reused while this
        # driver lives.
        reg = getattr(self, "_registered_name", None)
        if reg is not None:
            from ray_tpu._private.worker import _try_global_worker

            w = _try_global_worker()
            if w is not None and w.head_client is not None:
                try:
                    w.head_client.actor_deregister(*reg)
                except Exception:  # noqa: BLE001 — head gone at teardown
                    pass

    def join(self, timeout=None):
        self._thread.join(timeout)


class _ProcessActorProxy:
    """Stand-in for ``runtime.instance`` on process-backed actors: method
    access returns a callable that synchronously RPCs into the actor's
    worker process (used by compiled-DAG exec loops, which run driver-side
    but must execute stages against the real actor state)."""

    __slots__ = ("_rt",)

    def __init__(self, runtime: "_ActorRuntime"):
        self._rt = runtime

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        rt = self._rt

        def _call(*args, **kwargs):
            return rt._proxy_apply(name, args, kwargs)

        _call.__name__ = name
        return _call


def _resolve_values(worker, args, kwargs):
    """Resolve top-level ObjectRefs to values (actor init/arg semantics)."""

    def _resolve(v):
        if isinstance(v, ObjectRef):
            return worker.get_object(v)
        return v

    return (tuple(_resolve(a) for a in args),
            {k: _resolve(v) for k, v in kwargs.items()})


def _resolve_actor_args(worker, call: _MethodCall):
    def _resolve(v):
        if isinstance(v, ObjectRef):
            value = worker.get_object(v)
            return value
        return v

    return (
        tuple(_resolve(a) for a in call.args),
        {k: _resolve(v) for k, v in call.kwargs.items()},
    )


class ActorMethod:
    def __init__(self, runtime: _ActorRuntime, method_name: str,
                 options: Dict[str, Any]):
        self._runtime = runtime
        self._method_name = method_name
        self._options = options

    def options(self, **opts) -> "ActorMethod":
        merged = dict(self._options)
        merged.update(opts)
        return ActorMethod(self._runtime, self._method_name, merged)

    def remote(self, *args, **kwargs):
        num_returns = self._options.get("num_returns", 1)
        name = self._options.get(
            "name",
            f"{self._runtime.class_name}.{self._method_name}")
        if num_returns == "streaming":
            submit_stream = getattr(self._runtime, "submit_stream", None)
            if submit_stream is None:
                raise ValueError(
                    "num_returns='streaming' is not supported on "
                    "cluster-placed (remote-node) actors yet; use a "
                    "streaming task, or the serve KV stream fallback")
            return submit_stream(self._method_name, args, kwargs, name)
        refs = self._runtime.submit(
            self._method_name, args, kwargs, num_returns, name)
        return refs[0] if num_returns == 1 else refs

    def bind(self, *args, **kwargs):
        from ray_tpu.dag.dag_node import ClassMethodNode

        return ClassMethodNode(self, args, kwargs)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"Actor method {self._method_name!r} cannot be called directly; "
            f"use .remote().")


class ActorHandle:
    def __init__(self, runtime: _ActorRuntime):
        self._runtime = runtime
        self._actor_id = runtime.actor_id

    @property
    def _ray_actor_id(self) -> ActorID:
        return self._actor_id

    def __getattr__(self, item: str):
        if item.startswith("_"):
            raise AttributeError(item)
        cls = self._runtime.cls
        if cls is None:
            # Borrowed cluster actor whose class is not importable here:
            # method existence is validated by the hosting node instead.
            return ActorMethod(self._runtime, item, {})
        fn = getattr(cls, item, None)
        if fn is None:
            raise AttributeError(
                f"actor {self._runtime.class_name!r} has no method {item!r}")
        method_opts = getattr(fn, "__ray_tpu_method_options__", {})
        return ActorMethod(self._runtime, item, dict(method_opts))

    def __reduce__(self):
        return (_rebuild_handle, (self._actor_id,))

    def __repr__(self):
        return (f"ActorHandle({self._runtime.class_name}, "
                f"{self._actor_id.hex()[:12]}…)")


def _rebuild_handle(actor_id: ActorID) -> ActorHandle:
    worker = global_worker()
    from ray_tpu._private.client_worker import ClientActorHandle, ClientWorker

    if isinstance(worker, ClientWorker):
        # Handle crossed into a worker process: method calls go back
        # through the driver's API service.
        return ClientActorHandle(actor_id)
    # A handle to a cluster-placed actor may have crossed onto this
    # driver (pickled into a task pushed to another node, or resolved
    # by name): borrow it — calls go direct to the hosting node.
    from ray_tpu._private.remote_actor import resolve_or_borrow

    runtime = resolve_or_borrow(worker, actor_id)
    if runtime is None:
        raise RayActorError(actor_id, "actor not found on this node")
    return ActorHandle(runtime)


class ActorClass:
    def __init__(self, cls: type, options: Dict[str, Any]):
        self._cls = cls
        self._options = options

    def options(self, **opts) -> "ActorClass":
        merged = dict(self._options)
        merged.update(opts)
        return ActorClass(self._cls, merged)

    def remote(self, *args, **kwargs) -> ActorHandle:
        worker = auto_init()
        from ray_tpu._private.client_worker import (
            ClientActorHandle,
            ClientWorker,
        )

        if isinstance(worker, ClientWorker):
            # Inside a worker process: the driver owns all actor runtimes.
            actor_id = worker.actor_create(
                self._cls, args, kwargs, self._options)
            return ClientActorHandle(actor_id, self._cls.__name__)
        opts = self._options
        actor_name = opts.get("name")
        namespace = opts.get("namespace",
                             getattr(worker, "namespace", "default"))
        if actor_name:
            key = (namespace, actor_name)
            existing = worker.named_actors.get(key)
            if existing is not None and not existing._runtime.dead:
                if opts.get("get_if_exists"):
                    return existing
                raise ValueError(
                    f"actor name {actor_name!r} already taken in namespace "
                    f"{namespace!r}")
        actor_id = ActorID.of(
            worker.job_id, worker.current_task_id(),
            worker.actor_counter.next())
        if actor_name and worker.head_client is not None:
            # Reserve the cluster-wide name BEFORE building the runtime:
            # a rejection must not leave a live orphaned actor claiming
            # the name locally.
            worker.head_client.actor_register(
                namespace, actor_name, actor_id.binary(),
                self._cls.__name__)
        max_restarts = opts.get("max_restarts")
        if max_restarts is None:
            max_restarts = GlobalConfig.actor_max_restarts
        max_concurrency = opts.get("max_concurrency")
        try:
            # Cluster placement: the router decides whether this actor
            # lives locally or on a node daemon (resources / affinity /
            # SPREAD / thin-client — GcsActorScheduler role). A remote
            # placement builds a RemoteActorRuntime whose calls go
            # direct-to-node.
            node = None
            if worker.remote_router is not None:
                node = worker.remote_router.place_actor(opts)
            if node is not None:
                from ray_tpu._private.remote_actor import RemoteActorRuntime

                runtime = RemoteActorRuntime(
                    worker, actor_id, self._cls, args, kwargs,
                    node=node,
                    max_restarts=max_restarts,
                    max_concurrency=max_concurrency,
                    actor_name=actor_name,
                    opts=opts,
                    registered_name=(
                        (namespace, actor_name) if actor_name else None),
                )
            else:
                runtime = _ActorRuntime(
                    actor_id, self._cls, args, kwargs,
                    max_concurrency=max_concurrency,
                    max_restarts=max_restarts,
                    name=self._cls.__name__,
                    actor_name=actor_name,
                    runtime_target=opts.get("runtime"),
                    num_tpus=chips_requested(opts),
                )
        except BaseException:
            if actor_name and worker.head_client is not None:
                # Release the reserved cluster-wide name on construction
                # failure, or retries fail "already taken" forever.
                try:
                    worker.head_client.actor_deregister(
                        namespace, actor_name)
                except Exception:  # noqa: BLE001
                    pass
            raise
        worker.actors[actor_id] = runtime
        handle = ActorHandle(runtime)
        if actor_name:
            worker.named_actors[(namespace, actor_name)] = handle
            runtime._registered_name = (namespace, actor_name)
        return handle

    def bind(self, *args, **kwargs):
        from ray_tpu.dag.dag_node import ClassNode

        return ClassNode(self, args, kwargs)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"Actor class {self._cls.__name__!r} cannot be instantiated "
            f"directly; use {self._cls.__name__}.remote().")


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    worker = global_worker()
    from ray_tpu._private.client_worker import ClientActorHandle, ClientWorker

    if isinstance(worker, ClientWorker):
        return ClientActorHandle(worker.actor_named(name, namespace), name)
    ns = namespace or getattr(worker, "namespace", "default")
    handle = worker.named_actors.get((ns, name))
    if handle is not None and not handle._runtime.dead:
        return handle
    if worker.head_client is not None:
        entry = worker.head_client.actor_lookup(ns, name)
        if entry is not None:
            owner_id, actor_bin, class_name = entry
            if owner_id != worker.head_client.client_id:
                # Prefer the placement directory: a cluster-placed actor
                # is callable direct-to-node from ANY driver, bypassing
                # the owner-driver relay entirely.
                from ray_tpu._private.remote_actor import resolve_or_borrow

                runtime = resolve_or_borrow(worker, ActorID(bytes(actor_bin)))
                if runtime is not None:
                    return ActorHandle(runtime)
                return CrossDriverActorHandle(
                    owner_id, bytes(actor_bin), class_name)
    raise ValueError(
        f"no live actor named {name!r} in namespace {ns!r}")


class CrossDriverActorHandle:
    """Handle to a named actor owned by ANOTHER driver attached to the
    same head service. Method calls relay through the head to the owning
    driver and resolve to VALUES (plain args only — ObjectRefs do not
    cross drivers; pass values or announced objects)."""

    def __init__(self, owner_id: str, actor_bin: bytes, class_name: str):
        self._owner_id = owner_id
        self._actor_bin = actor_bin
        self._class_name = class_name

    def __getattr__(self, item: str):
        if item.startswith("_"):
            raise AttributeError(item)
        return _CrossDriverMethod(self, item)

    def __repr__(self):
        return (f"CrossDriverActorHandle({self._class_name}, "
                f"owner={self._owner_id})")


class _CrossDriverMethod:
    def __init__(self, handle: CrossDriverActorHandle, method: str):
        self._handle = handle
        self._method = method

    def remote(self, *args, **kwargs):
        worker = global_worker()
        h = self._handle
        oid = ObjectID.for_put(worker.current_task_id(),
                               worker.put_counter.next())
        ref = ObjectRef(oid)

        def _run():
            try:
                oid_bins = worker.head_client.actor_call(
                    h._owner_id, h._actor_bin, self._method, args, kwargs,
                    1)
                # The relay returned result IDS; the bytes move p2p from
                # the owner's object server (head-relayed chunks as
                # fallback) — large results never ride the event channel.
                raw = worker.head_client.object_pull(oid_bins[0])
                if raw is None:
                    raise ActorDiedError(
                        None, "cross-driver call result vanished before "
                        "it could be pulled (owner died?)")
                from ray_tpu._private.serialization import SerializedObject

                worker.store.put(oid, SerializedObject.from_bytes(raw))
            except BaseException as exc:  # noqa: BLE001 — relay boundary
                worker.store.put_error(oid, exc)

        threading.Thread(target=_run, daemon=True,
                         name="ray_tpu_cross_driver_call").start()
        return ref


def kill(actor: ActorHandle, *, no_restart: bool = True):
    if not isinstance(actor, ActorHandle):
        raise TypeError(f"kill() expects an ActorHandle, got {type(actor)}")
    actor._runtime.terminate(no_restart=no_restart)
