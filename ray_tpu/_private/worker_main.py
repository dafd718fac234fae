"""Worker process entrypoint: the leased-worker execution loop.

Rebuild of the reference's worker process main (reference role:
python/ray/_private/workers/default_worker.py + the CoreWorker task
execution loop it enters [unverified]). The driver's WorkerPool spawns this
module as a subprocess per worker; requests arrive over a shared-memory
mutable-object channel (the plasma-mutable-object analogue), argument and
result payloads ride the shared-memory object store, and replies go back on
a second channel. A ``kill -9`` of this process is detected by the driver
through process liveness + reply timeout and surfaces as
``WorkerCrashedError`` — never as a driver crash.
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys
import traceback
from typing import Any, Dict, List, Optional

from ray_tpu._private import tracing


class _ShmRef:
    """Marker for an argument stored in the shm object store."""

    __slots__ = ("key",)

    def __init__(self, key: int):
        self.key = key


def _fetch_blob(store, field):
    """Inverse of worker_pool.maybe_stage: ('shm', key) markers resolve
    through the store (the driver deletes the key after the reply)."""
    if isinstance(field, tuple) and len(field) == 2 and field[0] == "shm":
        return bytes(store.get(field[1]))
    return field


def _load_payload(store, ctx, payload: bytes):
    """Deserialize (args, kwargs), fetching _ShmRef args from the store."""
    from ray_tpu._private.serialization import SerializedObject

    args, kwargs = pickle.loads(payload)

    def _fetch(v):
        if isinstance(v, _ShmRef):
            raw = bytes(store.get(v.key))
            return ctx.deserialize(SerializedObject.from_bytes(raw))
        return v

    return (tuple(_fetch(a) for a in args),
            {k: _fetch(v) for k, v in kwargs.items()})


def _store_outputs(store, ctx, return_keys: List[int], result: Any,
                   num_returns: int):
    if num_returns <= 1:
        outputs = [result]
    else:
        outputs = list(result)
        if len(outputs) != num_returns:
            raise ValueError(
                f"task declared num_returns={num_returns} but returned "
                f"{len(outputs)} values")
    for key, value in zip(return_keys, outputs):
        store.put(key, ctx.serialize(value).to_bytes())


def _run_dag_stages(store, desc: dict, actor_instance) -> None:
    """Worker-resident compiled-DAG exec loop over shm channels.

    Never raises: any failure is logged to stderr (the driver's log
    plane) and terminates the loop — the caller must reply exactly once
    on the request channel, so a stray exception here must not reach the
    main loop's error boundary (double reply = protocol desync).
    """
    from ray_tpu.channels.channel import ShmBufferedChannel
    from ray_tpu.dag.compiled_dag import _Stage
    from ray_tpu.exceptions import ChannelError, ChannelTimeoutError

    try:
        chans = {cid: ShmBufferedChannel.attach(store, spec)
                 for cid, spec in desc["channels"].items()}
        stages = []
        for sd in desc["stages"]:
            sources = []
            for kind, a, b in sd["arg_sources"]:
                if kind == "const":
                    sources.append(("const", pickle.loads(a), None))
                else:
                    sources.append(("chan", chans[a], b))
            stages.append(_Stage(
                node=None, fn=None, arg_sources=sources,
                out_channel=chans[sd["out_channel"]],
                method_name=sd["method_name"]))
        while True:
            try:
                for stage in stages:
                    stage.run_once(actor_instance)
            except ChannelTimeoutError:
                if os.getppid() == 1:
                    return  # orphaned: the driver died without teardown
                continue  # producer/consumer slow: retry
            except ChannelError:
                return  # teardown closed the channels
    except BaseException:  # noqa: BLE001 — log, never propagate
        print("ray_tpu compiled-DAG worker loop failed:\n"
              + traceback.format_exc(), file=sys.stderr, flush=True)


def _run_stream_yields(gen, ctx, max_msg: int, stage_result, emit,
                       budget: int, wait_acks):
    """Producer yield loop shared by every process-plane stream flavor
    (task_stream, actor_stream, mux actor items): serialize each yield,
    ``emit`` it (small items inline in the frame, big items staged in the
    shm store), then run the pause protocol — ``wait_acks(count)`` blocks
    while committed-but-unconsumed items have reached ``budget`` and
    returns False when the consumer cancelled. Returns
    ``(total, cancelled)``."""
    limit = max(max_msg // 4, 64 * 1024)
    if not hasattr(gen, "__iter__") and not hasattr(gen, "__next__"):
        raise TypeError(
            f"streaming task returned non-iterable {type(gen).__name__}")
    it = iter(gen)
    idx = 0
    try:
        for item in it:
            raw = ctx.serialize(item).to_bytes()
            field = ("shm", stage_result(raw)) if len(raw) > limit else raw
            emit(idx, field)
            idx += 1
            if not wait_acks(idx):
                return idx, True
    except BaseException:
        close = getattr(it, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 — generator cleanup
                pass
        raise
    return idx, False


def worker_loop(store_name: str, req_id: int, rep_id: int,
                worker_id: int, max_msg: int,
                api_req_id: int = 0, api_rep_id: int = 0,
                ack_id: int = 0) -> None:
    import cloudpickle

    from ray_tpu._native.store import NativeMutableChannel, NativeObjectStore
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.ids import TaskID
    from ray_tpu._private.serialization import SerializationContext
    from ray_tpu.exceptions import ChannelError, ChannelTimeoutError, \
        RayTaskError

    store = NativeObjectStore.open(store_name)
    req = NativeMutableChannel(store, req_id, max_size=max_msg,
                               num_readers=1, create=False)
    rep = NativeMutableChannel(store, rep_id, max_size=max_msg,
                               num_readers=1, create=False)
    ack = None
    if ack_id:
        # Streaming backpressure acks (driver -> this worker); read only
        # inside a stream's pause/poll points, so it never interleaves
        # with the request protocol.
        ack = NativeMutableChannel(store, ack_id, max_size=8192,
                                   num_readers=1, create=False)

    # Install the client-mode runtime so ray_tpu.* API calls made inside
    # task/actor code forward to the driver instead of booting a second
    # full runtime in this process.
    if api_req_id and api_rep_id:
        from ray_tpu._private.client_worker import ClientWorker

        api_req = NativeMutableChannel(store, api_req_id, max_size=max_msg,
                                       num_readers=1, create=False)
        api_rep = NativeMutableChannel(store, api_rep_id, max_size=max_msg,
                                       num_readers=1, create=False)
        worker_mod._global_worker = ClientWorker(
            store, api_req, api_rep, worker_id)

    ctx = SerializationContext()
    fn_cache: Dict[bytes, Any] = {}
    actor_instance: Optional[Any] = None
    actor_state: Dict[str, Any] = {}  # concurrency plane for actor_new2
    import threading as _threading_mod

    rep_lock = _threading_mod.Lock()
    _stage_counter = [0]
    _stage_lock = _threading_mod.Lock()  # concurrent actor calls stage too

    def _reply(msg):
        with rep_lock:
            rep.write(msg)

    def _stage_result(raw: bytes) -> int:
        with _stage_lock:
            _stage_counter[0] += 1
            n = _stage_counter[0]
        key = (0xA4D0_0000_0000_0000
               | (os.getpid() & 0xFFFFFF) << 24
               | (n & 0xFF_FFFF))
        store.put(key, raw)
        return key

    def _finish_actor_call(call_id, result, return_keys, num_returns):
        if return_keys:
            _store_outputs(store, ctx, return_keys, result, num_returns)
            _reply(("calldone", call_id, "ok", None))
        else:
            raw = ctx.serialize(result).to_bytes()
            if len(raw) > max(max_msg // 4, 64 * 1024):
                _reply(("calldone", call_id, "okshm", _stage_result(raw)))
            else:
                _reply(("calldone", call_id, "okv", raw))

    def _fail_actor_call(call_id, name, exc):
        try:
            err = RayTaskError.from_exception(str(name), exc)
            _reply(("calldone", call_id, "err", pickle.dumps(err)))
        except Exception:  # noqa: BLE001 — unpicklable cause fallback
            err = RayTaskError(str(name), traceback.format_exc(), cause=None)
            _reply(("calldone", call_id, "err", pickle.dumps(err)))

    def _stream_actor_result(call_id, result, task_id_bin, budget,
                             wait_acks):
        """Emit one actor call's generator result as mux item frames."""
        total, cancelled = _run_stream_yields(
            result, ctx, max_msg, _stage_result,
            lambda i, f: _reply(("calldone", call_id, "item", (i, f))),
            budget, wait_acks)
        _reply(("calldone", call_id,
                "cancelled" if cancelled else "ok_stream", total))

    def _run_actor_call_sync(call_id, method_name, payload, return_keys,
                             num_returns, task_id_bin, name,
                             stream_budget=None):
        try:
            method = getattr(actor_instance, method_name)
            args, kwargs = _load_payload(store, ctx,
                                         _fetch_blob(store, payload))
            _set_task_ctx(task_id_bin, name)
            try:
                result = method(*args, **kwargs)
                if stream_budget is not None:
                    _stream_actor_result(
                        call_id, result, task_id_bin, stream_budget,
                        _mux_ack_waiter(task_id_bin, stream_budget))
                    return
            finally:
                _set_task_ctx(None, None)
                if stream_budget is not None:
                    _mux_stream_done(task_id_bin)
            _finish_actor_call(call_id, result, return_keys, num_returns)
        except BaseException as exc:  # noqa: BLE001 — call error boundary
            _fail_actor_call(call_id, name, exc)

    async def _run_actor_call_async(call_id, method_name, payload,
                                    return_keys, num_returns, task_id_bin,
                                    name, stream_budget=None):
        import inspect as _inspect

        try:
            method = getattr(actor_instance, method_name)
            args, kwargs = _load_payload(store, ctx,
                                         _fetch_blob(store, payload))
            _set_task_ctx(task_id_bin, name)
            try:
                result = method(*args, **kwargs)
                if _inspect.iscoroutine(result):
                    result = await result
                if stream_budget is not None:
                    if hasattr(result, "__anext__"):
                        await _stream_actor_result_async(
                            call_id, result, task_id_bin, stream_budget)
                    else:
                        # Sync generator from an async actor: iterate on
                        # the executor so the event loop stays live.
                        import asyncio as _asyncio

                        await _asyncio.get_running_loop().run_in_executor(
                            None, _stream_actor_result, call_id, result,
                            task_id_bin, stream_budget,
                            _mux_ack_waiter(task_id_bin, stream_budget))
                    return
            finally:
                _set_task_ctx(None, None)
                if stream_budget is not None:
                    _mux_stream_done(task_id_bin)
            _finish_actor_call(call_id, result, return_keys, num_returns)
        except BaseException as exc:  # noqa: BLE001 — call error boundary
            _fail_actor_call(call_id, name, exc)

    async def _stream_actor_result_async(call_id, agen, task_id_bin,
                                         budget):
        """Async-generator flavor of the mux item stream (pause points
        poll the ack table without blocking the event loop)."""
        import asyncio as _asyncio

        limit = max(max_msg // 4, 64 * 1024)
        key = bytes(task_id_bin)
        idx = 0
        cancelled = False
        async for item in agen:
            raw = ctx.serialize(item).to_bytes()
            field = ("shm", _stage_result(raw)) if len(raw) > limit \
                else raw
            _reply(("calldone", call_id, "item", (idx, field)))
            idx += 1
            while True:
                with _stream_ack_cv:
                    if key in _stream_cancels:
                        cancelled = True
                        break
                    if not budget or \
                            idx - _stream_acks.get(key, 0) < budget:
                        break
                await _asyncio.sleep(0.02)
            if cancelled:
                break
        _reply(("calldone", call_id,
                "cancelled" if cancelled else "ok_stream", idx))

    def _set_task_ctx(task_id_bin, name):
        worker_mod._task_context.current_task_id = (
            TaskID(task_id_bin) if task_id_bin else None)
        worker_mod._task_context.task_name = name
        # Feed the flight recorder's task-stuck watchdog: a task still
        # executing past flight_task_stuck_s auto-dumps this worker's
        # stacks without operator action (one `is None` branch when
        # the recorder is disarmed).
        from ray_tpu._private import flight as _flight

        if _flight._FLIGHT is not None:
            if task_id_bin:
                _flight.note_task_started(name or "task")
            else:
                _flight.note_task_finished()

    # ------------------------------------------------- streaming producers
    # Mux actors receive acks as ("stream_ack", tid_bin, n) REQUESTS on
    # the req channel (the main loop below drains it continuously); the
    # single-flight planes (task_stream / actor_stream) read the dedicated
    # ack channel inside their pause loop.
    _stream_acks: Dict[bytes, int] = {}
    _stream_cancels: set = set()
    _stream_ack_cv = _threading_mod.Condition()

    def _ack_chan_waiter(tid_bin: bytes, budget: int):
        """wait_acks over the dedicated ack channel (task_stream /
        actor_stream): drain opportunistically between yields, block at
        the budget. Stale acks from a previous stream on this worker are
        read and ignored (tid-tagged)."""
        acked = [0]
        cancelled = [False]

        def _drain(timeout: float) -> bool:
            if ack is None:
                return False
            try:
                m = ack.read(timeout=timeout)
            except ChannelTimeoutError:
                return False
            except ChannelError:
                cancelled[0] = True  # driver tore the channel down
                return False
            if m and m[0] == "stream_ack" and bytes(m[1]) == tid_bin:
                n = m[2]
                if n < 0:
                    cancelled[0] = True
                elif n > acked[0]:
                    acked[0] = n
            return True

        def wait_acks(count: int) -> bool:
            while _drain(0.001):
                pass
            while budget and count - acked[0] >= budget \
                    and not cancelled[0]:
                if not _drain(0.2) and os.getppid() == 1:
                    cancelled[0] = True  # orphaned: driver died
            return not cancelled[0]

        return wait_acks

    def _mux_ack_waiter(tid_bin: bytes, budget: int):
        """wait_acks over the main-loop-maintained ack table (mux
        actors: many streams share one worker process)."""
        key = bytes(tid_bin)

        def wait_acks(count: int) -> bool:
            with _stream_ack_cv:
                while True:
                    if key in _stream_cancels:
                        return False
                    if not budget or \
                            count - _stream_acks.get(key, 0) < budget:
                        return True
                    _stream_ack_cv.wait(0.2)
                    if os.getppid() == 1:
                        return False

        return wait_acks

    def _mux_stream_done(tid_bin: bytes):
        key = bytes(tid_bin)
        with _stream_ack_cv:
            _stream_acks.pop(key, None)
            _stream_cancels.discard(key)

    while True:
        try:
            msg = req.read(timeout=5.0)
        except ChannelTimeoutError:
            # Liveness escape hatch: if the parent died, exit.
            if os.getppid() == 1:
                return
            continue
        except ChannelError:
            return

        kind = msg[0]
        try:
            if kind == "exit":
                _reply(("ok", None))
                return
            elif kind == "ping":
                _reply(("ok", os.getpid()))
            elif kind == "task":
                (_, digest, fn_bytes, payload, return_keys, num_returns,
                 task_id_bin, name, env_fields) = msg[:9]
                trace_wire = msg[9] if len(msg) > 9 else None
                fn = fn_cache.get(digest)
                if fn is None:
                    fn = cloudpickle.loads(_fetch_blob(store, fn_bytes))
                    fn_cache[digest] = fn
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                _set_task_ctx(task_id_bin, name)
                span = tracing.begin(
                    "worker.exec", parent=tracing.extract(trace_wire),
                    task=name) if trace_wire is not None else None
                try:
                    if env_fields:
                        renv = _cached_runtime_env(env_fields)
                        with renv.applied():
                            result = fn(*args, **kwargs)
                    else:
                        result = fn(*args, **kwargs)
                except BaseException:
                    tracing.finish(span, status="error")
                    span = None
                    raise
                finally:
                    tracing.finish(span)
                    _set_task_ctx(None, None)
                _store_outputs(store, ctx, return_keys, result, num_returns)
                _reply(("ok", None))
            elif kind == "actor_new":
                _, cls_bytes, payload = msg
                cls = cloudpickle.loads(_fetch_blob(store, cls_bytes))
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                actor_instance = cls(*args, **kwargs)
                _reply(("ok", None))
            elif kind == "actor_new2":
                # Concurrent actor plane: async actors get a dedicated
                # asyncio loop thread, threaded actors a pool; calls arrive
                # as fire-and-forget "actor_submit" and complete out of
                # order as ("calldone", call_id, ...) on the reply channel.
                import threading as _threading

                _, cls_bytes, payload, mode, max_concurrency = msg
                cls = cloudpickle.loads(_fetch_blob(store, cls_bytes))
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                actor_instance = cls(*args, **kwargs)
                actor_state["mode"] = mode
                if mode == "async":
                    import asyncio as _asyncio

                    loop = _asyncio.new_event_loop()
                    sem = _asyncio.Semaphore(max(int(max_concurrency), 1))

                    def _loop_main():
                        _asyncio.set_event_loop(loop)
                        loop.run_forever()

                    t = _threading.Thread(target=_loop_main, daemon=True,
                                          name="actor-async-loop")
                    t.start()
                    actor_state["loop"] = loop
                    actor_state["sem"] = sem
                else:
                    from concurrent.futures import ThreadPoolExecutor

                    actor_state["pool"] = ThreadPoolExecutor(
                        max_workers=max(int(max_concurrency), 1),
                        thread_name_prefix="actor-call")
                _reply(("ok", None))
            elif kind == "dag_exec":
                # Compiled-DAG shm plane (reference: do_exec_tasks over
                # NCCL/shm channels): run this actor's static stage
                # schedule INSIDE the worker, reading/writing native shm
                # channels directly — the driver never touches the
                # inter-stage payloads. Blocks until the DAG tears down
                # (channels closed), which is the "DAG occupies the
                # actor" semantic; the reply releases the caller.
                try:
                    desc = pickle.loads(_fetch_blob(store, msg[1]))
                    _run_dag_stages(store, desc, actor_instance)
                except BaseException:  # noqa: BLE001 — must not reach the
                    # outer error boundary: that would send a SECOND reply
                    # and desync every later request on this worker.
                    print("ray_tpu dag_exec setup failed:\n"
                          + traceback.format_exc(), file=sys.stderr,
                          flush=True)
                finally:
                    _reply(("ok", None))
            elif kind == "actor_submit":
                (_, call_id, method_name, payload, return_keys,
                 num_returns, task_id_bin, name) = msg[:8]
                stream_budget = msg[8] if len(msg) > 8 else None
                if actor_instance is None:
                    _fail_actor_call(call_id, name, RuntimeError(
                        "actor_submit before actor_new2"))
                elif actor_state.get("mode") == "async":
                    import asyncio as _asyncio

                    loop = actor_state["loop"]
                    sem = actor_state["sem"]

                    async def _gated(call_id=call_id,
                                     method_name=method_name,
                                     payload=payload,
                                     return_keys=return_keys,
                                     num_returns=num_returns,
                                     task_id_bin=task_id_bin, name=name,
                                     stream_budget=stream_budget):
                        async with sem:
                            await _run_actor_call_async(
                                call_id, method_name, payload, return_keys,
                                num_returns, task_id_bin, name,
                                stream_budget)

                    _asyncio.run_coroutine_threadsafe(_gated(), loop)
                else:
                    actor_state["pool"].submit(
                        _run_actor_call_sync, call_id, method_name,
                        payload, return_keys, num_returns, task_id_bin,
                        name, stream_budget)
            elif kind == "stream_ack":
                # Mux-actor backpressure: consumption watermark (n >= 0)
                # or cancel (n < 0) for one in-flight stream. Fire and
                # forget — no reply.
                _, tid_bin, n = msg
                key = bytes(tid_bin)
                with _stream_ack_cv:
                    if n < 0:
                        _stream_cancels.add(key)
                    elif n > _stream_acks.get(key, 0):
                        _stream_acks[key] = n
                    _stream_ack_cv.notify_all()
            elif kind == "task_stream":
                (_, digest, fn_bytes, payload, task_id_bin, name,
                 env_fields, budget) = msg
                fn = fn_cache.get(digest)
                if fn is None:
                    fn = cloudpickle.loads(_fetch_blob(store, fn_bytes))
                    fn_cache[digest] = fn
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                _set_task_ctx(task_id_bin, name)
                try:
                    def _go():
                        gen = fn(*args, **kwargs)
                        total, was_cancelled = _run_stream_yields(
                            gen, ctx, max_msg, _stage_result,
                            lambda i, f: _reply(("item", i, f)),
                            budget,
                            _ack_chan_waiter(bytes(task_id_bin), budget))
                        _reply(("cancelled",) if was_cancelled
                               else ("ok", total))

                    if env_fields:
                        renv = _cached_runtime_env(env_fields)
                        with renv.applied():
                            _go()
                    else:
                        _go()
                finally:
                    _set_task_ctx(None, None)
            elif kind == "actor_stream":
                # Streaming method on a sync (non-mux) process actor: the
                # same wire shape as task_stream, generator from the
                # resident instance.
                (_, method_name, payload, task_id_bin, name, budget) = msg
                if actor_instance is None:
                    raise RuntimeError("actor_stream before actor_new")
                method = getattr(actor_instance, method_name)
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                _set_task_ctx(task_id_bin, name)
                try:
                    gen = method(*args, **kwargs)
                    total, was_cancelled = _run_stream_yields(
                        gen, ctx, max_msg, _stage_result,
                        lambda i, f: _reply(("item", i, f)),
                        budget, _ack_chan_waiter(bytes(task_id_bin),
                                                 budget))
                    _reply(("cancelled",) if was_cancelled
                           else ("ok", total))
                finally:
                    _set_task_ctx(None, None)
            elif kind == "actor_call":
                (_, method_name, payload, return_keys, num_returns,
                 task_id_bin, name) = msg
                if actor_instance is None:
                    raise RuntimeError("actor_call before actor_new")
                method = getattr(actor_instance, method_name)
                args, kwargs = _load_payload(store, ctx,
                                             _fetch_blob(store, payload))
                _set_task_ctx(task_id_bin, name)
                try:
                    result = method(*args, **kwargs)
                finally:
                    _set_task_ctx(None, None)
                if return_keys:
                    _store_outputs(store, ctx, return_keys, result,
                                   num_returns)
                    _reply(("ok", None))
                else:
                    # Proxy apply (DAG exec loop): result rides the reply;
                    # big results stage through the store instead.
                    raw = ctx.serialize(result).to_bytes()
                    if len(raw) > max(max_msg // 4, 64 * 1024):
                        _reply(("okshm", _stage_result(raw)))
                    else:
                        _reply(("ok", raw))
            else:
                raise ValueError(f"unknown request kind {kind!r}")
        except BaseException as exc:  # noqa: BLE001 — worker error boundary
            if kind in ("actor_call", "actor_stream"):
                name = msg[1]
            elif kind == "task_stream":
                name = msg[5]
            else:
                name = "task"
            try:
                err = RayTaskError.from_exception(str(name), exc)
                _reply(("err", pickle.dumps(err)))
            except Exception:  # noqa: BLE001 — unpicklable cause fallback
                err = RayTaskError(str(name), traceback.format_exc(),
                                   cause=None)
                _reply(("err", pickle.dumps(err)))


_renv_cache = {}


def _cached_runtime_env(env_fields):
    """One staged RuntimeEnv per distinct env per worker process: staging
    copies working_dir into a tempdir, which must not repeat (or leak)
    per task execution."""
    import pickle as _pickle

    from ray_tpu.runtime_env import RuntimeEnv

    fields = {k: v for k, v in env_fields.items()
              if k in ("env_vars", "working_dir", "py_modules", "pip")}
    key = _pickle.dumps(sorted(fields.items()))
    renv = _renv_cache.get(key)
    if renv is None:
        renv = RuntimeEnv(**fields).stage()
        _renv_cache[key] = renv
    return renv


def _install_pdeathsig() -> None:
    """Orphan fence (Linux): a pooled worker must never outlive the
    process that owns its shm store — a SIGKILLed hosting daemon
    (chaos node kills, reaped nodes, the head-failover episode's
    teardown) would otherwise leave workers spinning against dead
    channels forever, observed as CPU-burning orphans. The kernel
    delivers SIGKILL on parent death (PR_SET_PDEATHSIG), installed by
    the child itself so the spawn path needs no fork-unsafe
    preexec_fn. The parent-died-before-prctl race is closed by
    comparing getppid() against the SPAWNER's pid handed down in
    RAY_TPU_PARENT_PID — never against init's pid 1, which is the
    legitimate parent when the hosting daemon runs as a container's
    PID 1. Silently a no-op off Linux."""
    if not sys.platform.startswith("linux"):
        return
    try:
        import ctypes
        import signal as _signal

        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, int(_signal.SIGKILL), 0, 0, 0)
        spawner = os.environ.get("RAY_TPU_PARENT_PID")
        if spawner and os.getppid() != int(spawner):
            # Reparented before prctl landed: the spawner is already
            # gone and the death signal will never fire — exit now.
            os.kill(os.getpid(), _signal.SIGKILL)
    except Exception:  # noqa: BLE001 — fence is best-effort hardening
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", required=True)
    ap.add_argument("--req-id", type=int, required=True)
    ap.add_argument("--rep-id", type=int, required=True)
    ap.add_argument("--api-req-id", type=int, default=0)
    ap.add_argument("--api-rep-id", type=int, default=0)
    ap.add_argument("--ack-id", type=int, default=0)
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--max-msg", type=int, default=4 << 20)
    args = ap.parse_args(argv)
    _install_pdeathsig()
    # Tracing arms from the inherited environment; worker processes have
    # no dialable trace_dump server, so finished spans SPILL to the
    # hosting runtime's RAY_TPU_TRACE_DIR (merged by its trace_dump).
    tracing.install_from_env(component="worker", spill=True)
    # Flight recorder: same shape — bundle snapshots spill periodically
    # to the hosting runtime's RAY_TPU_FLIGHT_DIR (merged by its
    # debug_dump), since nothing can dial a worker process directly.
    from ray_tpu._private import flight

    flight.install_from_env(component="worker", spill=True)
    worker_loop(args.store, args.req_id, args.rep_id, args.worker_id,
                args.max_msg, args.api_req_id, args.api_rep_id,
                args.ack_id)
    return 0


if __name__ == "__main__":
    # Re-dispatch through the canonical import so _ShmRef has one class
    # identity (running under -m makes this module __main__, which would
    # otherwise break isinstance against driver-pickled markers).
    from ray_tpu._private import worker_main as _canonical

    sys.exit(_canonical.main())
