"""Worker process pool: spawn, lease, crash-detect, restart.

Rebuild of the reference's WorkerPool + worker leasing (reference roles:
src/ray/raylet/worker_pool.cc PopWorker/PushWorker and the owner-side lease
loop of NormalTaskSubmitter [unverified]). Workers are real OS processes
running ``ray_tpu._private.worker_main``; the driver leases one per task
(cached leases amortize nothing here because the channel handshake is the
whole cost), ships the task over a shm mutable-object channel, and detects
worker death via process liveness — so a crashed or ``kill -9``-ed worker
fails only its task (WorkerCrashedError), never the driver.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import queue
import subprocess
import sys
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.log import get_logger
from ray_tpu._private.worker_main import _ShmRef

log = get_logger(__name__)
from ray_tpu.exceptions import (
    ChannelError,
    ChannelTimeoutError,
    TaskCancelledError,
    WorkerCrashedError,
    WorkerPoolExhaustedError,
)

_INLINE_LIMIT = 256 * 1024  # args bigger than this ride the shm store


def _pump_stream(stream, path: str):
    """Copy one worker pipe into its session log file, line-buffered."""
    try:
        with open(path, "ab", buffering=0) as f:
            for chunk in iter(lambda: stream.readline(), b""):
                f.write(chunk)
    except Exception as exc:  # worker died mid-write
        log.debug("worker log pump for %s stopped: %r", path, exc)


def _try_owner_log_dir():
    """The driver session's log dir, if the runtime is up (workers spawned
    during Worker.__init__ resolve it via the config fallback)."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod._try_global_worker()
    if w is not None and getattr(w, "session_dir", None):
        return os.path.join(w.session_dir, "logs")
    return os.environ.get("RAY_TPU_SESSION_LOG_DIR")


class WorkerProcess:
    """One spawned worker + its request/reply channels."""

    _id_counter = [0]
    _id_lock = threading.Lock()

    def __init__(self, store, max_msg: int = 4 << 20,
                 env: Optional[Dict[str, str]] = None,
                 log_dir: Optional[str] = None,
                 python_exe: Optional[str] = None,
                 env_key: Optional[str] = None,
                 tpu_chips: Tuple[int, ...] = (),
                 host_chips: int = 0):
        from ray_tpu._native.store import NativeMutableChannel
        from ray_tpu._private.tpu_chips import worker_env

        # Chips this process was granted (of the host's ``host_chips``);
        # empty for the ordinary CPU-pinned worker.
        self.tpu_chips = tuple(tpu_chips)

        # Runtime-env binding: a pip env's venv interpreter + its content
        # key (None = the driver's interpreter / default sub-pool).
        self.python_exe = python_exe or sys.executable
        self.env_key = env_key
        with WorkerProcess._id_lock:
            WorkerProcess._id_counter[0] += 1
            self.worker_id = WorkerProcess._id_counter[0]
        self._store = store
        self.max_msg = max_msg
        # Channel object-ids live in a reserved high range so they never
        # collide with task-return/put objects (which hash full ObjectIDs).
        base = (0xC0FF_EE00_0000_0000
                | (os.getpid() & 0xFFFF) << 24 | self.worker_id << 4)
        self._req_id = base | 1
        self._rep_id = base | 2
        self._api_req_id = base | 3
        self._api_rep_id = base | 5
        self._ack_id = base | 6
        self._req = NativeMutableChannel(
            store, self._req_id, max_size=max_msg, num_readers=1)
        self._rep = NativeMutableChannel(
            store, self._rep_id, max_size=max_msg, num_readers=1)
        # Streaming backpressure acks (driver -> worker): a dedicated tiny
        # channel so consumption watermarks never interleave with task
        # requests on the req channel (a stale unread ack there would be
        # read as the next request and desync the protocol).
        self._ack = NativeMutableChannel(
            store, self._ack_id, max_size=8192, num_readers=1)
        # Reverse API channel pair: ray_tpu.* calls made inside the worker
        # forward to the driver's service thread (driver_service.py).
        self._api_req = NativeMutableChannel(
            store, self._api_req_id, max_size=max_msg, num_readers=1)
        self._api_rep = NativeMutableChannel(
            store, self._api_rep_id, max_size=max_msg, num_readers=1)
        cmd = [
            self.python_exe, "-m", "ray_tpu._private.worker_main",
            "--store", store.name,
            "--req-id", str(self._req_id),
            "--rep-id", str(self._rep_id),
            "--api-req-id", str(self._api_req_id),
            "--api-rep-id", str(self._api_rep_id),
            "--ack-id", str(self._ack_id),
            "--worker-id", str(self.worker_id),
            "--max-msg", str(max_msg),
        ]
        full_env = dict(os.environ)
        if env:
            full_env.update(env)
        # Workers never spawn their own pools (the driver owns the process
        # plane), and they must be able to import test/user modules the
        # driver loaded from sys.path-only locations.
        full_env["RAY_TPU_WORKER_MODE"] = "thread"
        # Device ownership (tpu_chips.py): a worker granted chips is the
        # one process that opens them; every other worker is pinned to
        # the CPU, whatever platform the driver's environment names.
        full_env.update(worker_env(self.tpu_chips, host_chips))
        self.spawn_env = full_env
        # Orphan-fence handshake: the worker compares getppid() against
        # THIS pid after installing PR_SET_PDEATHSIG (worker_main) —
        # proven reparenting, not the ppid==1 heuristic that would
        # false-positive when this process is a container's PID 1.
        full_env["RAY_TPU_PARENT_PID"] = str(os.getpid())
        extra_path = [p for p in sys.path if p]
        prev = full_env.get("PYTHONPATH", "")
        full_env["PYTHONPATH"] = os.pathsep.join(
            extra_path + ([prev] if prev else []))
        # Log plane: worker stdout/stderr land in per-worker session files
        # that the driver's LogMonitor tails back to the driver's stderr.
        self._log_files = []
        stdout = stderr = None
        if log_dir is None:
            owner = _try_owner_log_dir()
            log_dir = owner
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            self.proc = subprocess.Popen(cmd, env=full_env,
                                         stdout=subprocess.PIPE,
                                         stderr=subprocess.PIPE)
            # Re-open by pid AFTER spawn so the filename carries the real
            # worker pid; cheap copy threads drain the pipes into files.
            for stream, ext in ((self.proc.stdout, "out"),
                                (self.proc.stderr, "err")):
                path = os.path.join(
                    log_dir, f"worker-{self.worker_id}-{self.proc.pid}.{ext}")
                t = threading.Thread(
                    target=_pump_stream, args=(stream, path), daemon=True,
                    name=f"ray_tpu_logpump_{self.worker_id}_{ext}")
                t.start()
                self._log_files.append(path)
        else:
            self.proc = subprocess.Popen(cmd, env=full_env,
                                         stdout=stdout, stderr=stderr)
        self._dead = False
        self._svc_stop = False
        from ray_tpu._private.driver_service import service_loop

        self._svc_thread = threading.Thread(
            target=service_loop, args=(self,), daemon=True,
            name=f"ray_tpu_api_svc_{self.worker_id}")
        self._svc_thread.start()

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return not self._dead and self.proc.poll() is None

    def request(self, msg: Tuple, timeout: Optional[float] = None,
                cancel_event: Optional[threading.Event] = None):
        """Send one request and block for the reply.

        Polls in short slices so a dead worker (kill -9) is detected in
        ~200ms instead of hanging; raises WorkerCrashedError then.
        """
        if not self.alive():
            raise WorkerCrashedError(f"worker {self.pid} is dead")
        try:
            self._req.write(msg, timeout=timeout or 60.0)
        except (ChannelError, ChannelTimeoutError) as e:
            if not self.alive():
                raise WorkerCrashedError(
                    f"worker {self.pid} died before accepting the task"
                ) from e
            raise
        while True:
            try:
                status, value = self._rep.read(timeout=0.2)
                break
            except ChannelTimeoutError:
                if self.proc.poll() is not None:
                    self._dead = True
                    if cancel_event is not None and cancel_event.is_set():
                        raise TaskCancelledError()
                    raise WorkerCrashedError(
                        f"worker {self.pid} died mid-task "
                        f"(exit code {self.proc.returncode})")
        if status == "err":
            raise pickle.loads(value)
        if status == "okshm":
            data = bytes(self._store.get(value))
            self._store.delete(value)
            return data
        return value

    def kill(self):
        self._dead = True
        self._svc_stop = True
        try:
            self.proc.kill()
        except Exception:  # noqa: BLE001
            pass

    def shutdown(self, timeout: float = 2.0):
        self._svc_stop = True
        if self.alive():
            try:
                self._req.write(("exit",), timeout=0.5)
                self.proc.wait(timeout=timeout)
            except Exception:  # noqa: BLE001
                self.kill()
        else:
            self.kill()
        self._svc_thread.join(timeout=1.0)
        # The worker is dead: reclaim the channel arenas in the shm store.
        for ch in (self._req, self._rep, self._api_req, self._api_rep,
                   self._ack):
            ch.destroy()


class WorkerPool:
    """Prestarted worker processes with lease/return + crash replacement."""

    def __init__(self, store, num_workers: int, max_msg: int = 4 << 20,
                 max_workers: Optional[int] = None,
                 log_dir: Optional[str] = None,
                 host_chips: int = 0):
        self._store = store
        self._max_msg = max_msg
        self._log_dir = log_dir
        self._host_chips = host_chips
        self._lock = threading.Lock()
        self._idle: "queue.Queue[WorkerProcess]" = queue.Queue()
        # Pip runtime envs get their own idle queues: those workers run a
        # different interpreter and must never serve default-env tasks.
        self._env_idle: Dict[str, "queue.Queue[WorkerProcess]"] = {}
        self._all: List[WorkerProcess] = []
        self._shutdown = False
        self._spawning = 0  # growth slots reserved but not yet spawned
        self._base_workers = max(int(num_workers), 1)
        # Elastic cap: blocked workers (nested get() inside a task) hold
        # their lease, so the pool grows past the base size rather than
        # deadlocking — the reference's dynamic worker-start behavior.
        self._max_workers = max_workers or max(num_workers * 4, num_workers)
        # Workers spawn LAZILY on first demand: booting the whole base
        # pool up front serializes ~0.4s of interpreter startup per worker
        # on the CPU that init()'s caller is about to use.

    def _try_spawn(self, limit: int, python_exe: Optional[str] = None,
                   env_key: Optional[str] = None
                   ) -> Optional[WorkerProcess]:
        """Reserve a slot under `limit` and spawn outside the lock."""
        with self._lock:
            if (self._shutdown
                    or len(self._all) + self._spawning >= limit):
                return None
            self._spawning += 1
        try:
            fresh = WorkerProcess(self._store, max_msg=self._max_msg,
                                  log_dir=self._log_dir,
                                  python_exe=python_exe, env_key=env_key)
        except Exception:  # noqa: BLE001 — e.g. shm store full
            fresh = None
        with self._lock:
            self._spawning -= 1
            if fresh is not None and not self._shutdown:
                self._all.append(fresh)
                return fresh
        if fresh is not None:  # raced shutdown
            fresh.shutdown(timeout=0.1)
        return None

    def lease(self, timeout: float = 60.0,
              runtime_env=None,
              tpu_chips: Tuple[int, ...] = ()) -> WorkerProcess:
        import time as _time

        env_key = runtime_env.env_key() if runtime_env is not None else None
        if tpu_chips:
            # A task that holds chips gets a process of its own, started
            # with those chips visible and retired on release: an idle
            # pooled worker that had opened a chip would keep it from
            # every later claimant.
            if env_key is not None:
                raise ValueError(
                    "a pip runtime_env cannot be combined with TPU "
                    "resources")
            if self._shutdown:
                raise WorkerPoolExhaustedError("worker pool is shut down")
            w = WorkerProcess(self._store, max_msg=self._max_msg,
                              log_dir=self._log_dir, tpu_chips=tpu_chips,
                              host_chips=self._host_chips)
            with self._lock:
                self._all.append(w)
            return w
        if env_key is not None:
            return self._lease_env(runtime_env, env_key, timeout)
        deadline = _time.monotonic() + timeout
        while True:
            if self._shutdown:
                # A straggler task leasing against a shut-down pool must
                # fail NOW: with lazy spawning there is nothing idle and
                # nothing will ever spawn, and an executor thread spinning
                # out the full deadline blocks interpreter exit (the
                # thread-pool atexit join).
                raise WorkerPoolExhaustedError("worker pool is shut down")
            try:
                w = self._idle.get_nowait()
            except queue.Empty:
                # Below base size: spawn immediately, no wait.
                fresh = self._try_spawn(self._base_workers)
                if fresh is not None:
                    return fresh
            else:
                if w.alive():
                    return w
                self._replace(w)
                continue
            try:
                w = self._idle.get(timeout=0.5)
            except queue.Empty:
                # Elastic growth past the base (blocked workers holding
                # leases must not deadlock nested submissions); spawn
                # failure (e.g. shm store full) degrades to waiting.
                fresh = self._try_spawn(self._max_workers)
                if fresh is None:
                    # At cap but idle ENV workers exist: evict one — the
                    # mirror of _lease_env's default-worker eviction, so
                    # neither sub-pool can starve behind the other's
                    # reclaimable idle capacity.
                    evicted = self._evict_idle_env_worker()
                    if evicted:
                        fresh = self._try_spawn(self._max_workers)
                if fresh is not None:
                    return fresh
                if _time.monotonic() >= deadline:
                    raise WorkerPoolExhaustedError(
                        f"no idle worker within {timeout:.0f}s "
                        f"(pool size {self.size}); long-running tasks may "
                        f"be holding every worker") from None
                continue
            if w.alive():
                return w
            # Crashed while idle: replace and retry.
            self._replace(w)

    def _evict_idle_env_worker(self) -> bool:
        with self._lock:
            queues = list(self._env_idle.values())
        for q in queues:
            try:
                w = q.get_nowait()
            except queue.Empty:
                continue
            self._remove_dead(w)
            return True
        return False

    def _lease_env(self, runtime_env, env_key: str,
                   timeout: float) -> WorkerProcess:
        """Lease a worker bound to a pip runtime env. The venv build is
        lazy — the first lease pays it (reference role: runtime-env agent
        building before the lease is granted)."""
        import time as _time

        with self._lock:
            q = self._env_idle.setdefault(env_key, queue.Queue())
        python_exe = runtime_env.python_executable()  # builds on first use
        # Deadline starts AFTER the build: a 90s first pip install must
        # not eat the lease budget and fake pool exhaustion.
        deadline = _time.monotonic() + timeout
        while True:
            if self._shutdown:
                raise WorkerPoolExhaustedError("worker pool is shut down")
            try:
                w = q.get_nowait()
            except queue.Empty:
                fresh = self._try_spawn(self._max_workers,
                                        python_exe=python_exe,
                                        env_key=env_key)
                if fresh is None:
                    # Pool at cap but holding idle DEFAULT workers: evict
                    # one to make room — env demand must not starve
                    # behind reclaimable default capacity.
                    try:
                        idle_default = self._idle.get_nowait()
                    except queue.Empty:
                        pass
                    else:
                        self._remove_dead(idle_default)
                        fresh = self._try_spawn(self._max_workers,
                                                python_exe=python_exe,
                                                env_key=env_key)
                if fresh is not None:
                    return fresh
                try:
                    w = q.get(timeout=0.5)
                except queue.Empty:
                    if _time.monotonic() >= deadline:
                        raise WorkerPoolExhaustedError(
                            f"no idle worker for runtime env {env_key} "
                            f"within {timeout:.0f}s") from None
                    continue
            if w.alive():
                return w
            self._remove_dead(w)

    def _remove_dead(self, dead: WorkerProcess):
        with self._lock:
            try:
                self._all.remove(dead)
            except ValueError:
                pass
        dead.shutdown(timeout=0.1)

    def release(self, w: WorkerProcess):
        if self._shutdown:
            return
        if w.tpu_chips:
            # The chips are free once the process that opened them is
            # gone, and not before.
            self._remove_dead(w)
            try:
                w.proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                log.warning("TPU worker %s still alive 30s after kill",
                            w.pid)
            return
        if not w.alive():
            if w.env_key is not None:
                self._remove_dead(w)  # env workers respawn on demand
            else:
                self._replace(w)
            return
        if w.env_key is not None:
            with self._lock:
                q = self._env_idle.setdefault(w.env_key, queue.Queue())
            q.put(w)
        else:
            self._idle.put(w)

    def _replace(self, dead: WorkerProcess):
        with self._lock:
            if self._shutdown:
                return
            try:
                self._all.remove(dead)
            except ValueError:
                pass
            dead.shutdown(timeout=0.1)
            fresh = WorkerProcess(self._store, max_msg=self._max_msg,
                                  log_dir=self._log_dir)
            self._all.append(fresh)
            self._idle.put(fresh)

    @property
    def size(self) -> int:
        with self._lock:
            return len(self._all)

    def pids(self) -> List[int]:
        with self._lock:
            return [w.pid for w in self._all]

    def shutdown(self):
        import time as _time

        with self._lock:
            self._shutdown = True
            workers = list(self._all)
            self._all.clear()
        # Broadcast exits first, then reap against one shared deadline —
        # a serial per-worker wait turns every teardown into seconds.
        for w in workers:
            w._svc_stop = True
            if w.alive():
                try:
                    w._req.write(("exit",), timeout=0.05)
                except Exception:  # noqa: BLE001
                    w.kill()
            else:
                w.kill()
        deadline = _time.monotonic() + 1.0
        for w in workers:
            while w.proc.poll() is None and _time.monotonic() < deadline:
                _time.sleep(0.01)
            if w.proc.poll() is None:
                w.kill()
            w._svc_thread.join(timeout=0.5)
            for ch in (w._req, w._rep, w._api_req, w._api_rep, w._ack):
                try:
                    ch.destroy()
                except Exception:  # noqa: BLE001
                    pass


# ---------------------------------------------------------------------------
# Task payload packing (driver side)
# ---------------------------------------------------------------------------

# Keyed by the function OBJECT (weakly): an id()-keyed cache would serve a
# stale entry when CPython recycles the id of a collected function.
_fn_digest_cache: "weakref.WeakKeyDictionary[Any, Tuple[bytes, bytes]]" = (
    weakref.WeakKeyDictionary())
_fn_cache_lock = threading.Lock()


def pack_function(fn) -> Tuple[bytes, bytes]:
    """(digest, fn_bytes) with per-function caching; workers cache by
    digest so the bytes only cross once per (worker, function)."""
    import cloudpickle

    try:
        with _fn_cache_lock:
            hit = _fn_digest_cache.get(fn)
        if hit is not None:
            return hit
        cacheable = True
    except TypeError:  # unhashable callable
        cacheable = False
    data = cloudpickle.dumps(fn)
    digest = hashlib.sha1(data).digest()
    if cacheable:
        try:
            with _fn_cache_lock:
                _fn_digest_cache[fn] = (digest, data)
        except TypeError:  # not weakref-able: skip caching
            pass
    return digest, data


def oid_key(object_id) -> int:
    """Stable u64 key for an ObjectID in the shm store.

    Hashes the FULL id: the first 8 bytes alone are the task id prefix,
    shared by every return of a multi-return task."""
    digest = hashlib.blake2b(object_id.binary(), digest_size=8).digest()
    # Clear the top nibble so keys never collide with the reserved channel
    # (0xC…) and staging (0xA…) ranges.
    return int.from_bytes(digest, "little") & 0x0FFF_FFFF_FFFF_FFFF


_stage_counter = [0]
_stage_lock = threading.Lock()


def _next_stage_key() -> int:
    with _stage_lock:
        _stage_counter[0] += 1
        return 0xA4A0_0000_0000_0000 | (_stage_counter[0] & 0xFFFF_FFFF_FFFF)


def stage_blob(store, data: bytes) -> Tuple[Tuple[str, int], int]:
    """Stage an oversized message blob (function bytes / packed payload) in
    the shm store; returns the ('shm', key) marker and the key to delete
    after the reply."""
    key = _next_stage_key()
    store.put(key, data)
    return ("shm", key), key


def maybe_stage(store, data: bytes, limit: int):
    """Inline small blobs; stage big ones. Returns (field, staged_keys)."""
    if len(data) <= limit:
        return data, []
    marker, key = stage_blob(store, data)
    return marker, [key]


def fetch_blob(store, field) -> bytes:
    """Worker-side inverse of maybe_stage (driver deletes staged keys)."""
    if isinstance(field, tuple) and len(field) == 2 and field[0] == "shm":
        return bytes(store.get(field[1]))
    return field


def pack_args(store, ctx, args, kwargs) -> Tuple[bytes, List[int]]:
    """Pickle (args, kwargs); values too big to inline are staged in the
    shm store and replaced with _ShmRef markers the worker fetches.
    Returns (payload, staged_keys) — caller deletes the staged keys after
    the reply."""
    staged: List[int] = []

    def _pack(v):
        try:
            data = pickle.dumps(v, protocol=5)
        except Exception:  # noqa: BLE001 — fall back to rich serializer
            data = None
        if data is not None and len(data) <= _INLINE_LIMIT:
            return v
        serialized = ctx.serialize(v).to_bytes()
        key = _next_stage_key()
        store.put(key, serialized)
        staged.append(key)
        return _ShmRef(key)

    packed_args = tuple(_pack(a) for a in args)
    packed_kwargs = {k: _pack(v) for k, v in kwargs.items()}
    payload = pickle.dumps((packed_args, packed_kwargs), protocol=5)
    return payload, staged
