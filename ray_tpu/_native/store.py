"""Pythonic wrappers over the native library (object store, mutable
channels, task queue)."""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional

import numpy as np

from ray_tpu._native.build import load_native
from ray_tpu.exceptions import ChannelError, ChannelTimeoutError

_ERRS = {
    -1: "exists", -2: "not found", -3: "full", -4: "timeout",
    -5: "closed", -6: "bad state", -7: "system error",
}
_CLOSED = -5


class NativeError(RuntimeError):
    def __init__(self, code: int, op: str):
        super().__init__(f"native {op} failed: {_ERRS.get(code, code)}")
        self.code = code
        self.op = op

    def __reduce__(self):
        # Default exception reduce would call __init__(message) and crash
        # when the error crosses a process boundary.
        return (NativeError, (self.code, self.op))


def _check(code: int, op: str):
    if code == -4:
        raise ChannelTimeoutError(f"native {op} timed out")
    if code == -5:
        raise ChannelError(f"native {op}: channel closed")
    if code != 0:
        raise NativeError(code, op)


class NativeObjectStore:
    """Shared-memory object store (plasma-parity surface: put/get/contains/
    delete + mutable objects). ``create`` owns the segment; ``open``
    attaches from another process.

    ``close`` may race calls on other threads (a worker retired while
    the runtime shuts down, a reader blocked in ``mo_read``): calls in
    flight keep the segment mapped, the last of them unmaps it, and a
    call after ``close`` raises ``NativeError`` (closed) instead of
    handing the library a freed handle."""

    def __init__(self, handle, lib, owner: bool):
        self._h = handle
        self._lib = lib
        self._owner = owner
        self._calls = 0
        self._closing = False
        self._calls_lock = threading.Lock()

    @staticmethod
    def create(name: Optional[str] = None, capacity: int = 64 << 20,
               max_objects: int = 4096) -> "NativeObjectStore":
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable (no g++?)")
        name = name or f"/ray_tpu_store_{os.getpid()}_{id(lib) & 0xffff}"
        h = lib.rtn_store_create(name.encode(), capacity, max_objects)
        if not h:
            raise RuntimeError(f"failed to create shm store {name}")
        store = NativeObjectStore(h, lib, owner=True)
        store.name = name
        return store

    @staticmethod
    def open(name: str) -> "NativeObjectStore":
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        h = lib.rtn_store_open(name.encode())
        if not h:
            raise RuntimeError(f"failed to open shm store {name}")
        store = NativeObjectStore(h, lib, owner=False)
        store.name = name
        return store

    def _enter(self, op: str):
        """The handle, held until the matching ``_exit``."""
        with self._calls_lock:
            if self._closing:
                raise NativeError(_CLOSED, op)
            self._calls += 1
            return self._h

    def _exit(self):
        with self._calls_lock:
            self._calls -= 1
            h = self._h if self._closing and not self._calls else None
            if h:
                self._h = None
        if h:
            self._lib.rtn_store_close(h)

    def _call(self, op: str, fn, *args):
        h = self._enter(op)
        try:
            return fn(h, *args)
        finally:
            self._exit()

    def close(self):
        with self._calls_lock:
            if self._closing:
                return
            self._closing = True
            self._calls += 1  # held like a call: the last one out unmaps
        # The name goes now: the next runtime of this process creates its
        # store under the same name, which a late unmap must not unlink.
        self._lib.rtn_store_unlink(self._h)
        self._exit()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------- objects
    def put(self, object_id: int, data: bytes):
        _check(self._call("put", self._lib.rtn_put, object_id, data,
                          len(data)), "put")

    def get(self, object_id: int) -> bytes:
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        ln = ctypes.c_uint64()
        h = self._enter("get")
        try:
            _check(self._lib.rtn_get(h, object_id, ctypes.byref(ptr),
                                     ctypes.byref(ln)), "get")
            return ctypes.string_at(ptr, ln.value)
        finally:
            self._exit()

    def get_view(self, object_id: int) -> memoryview:
        """Zero-copy view into the shm segment (valid until delete)."""
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        ln = ctypes.c_uint64()
        _check(self._call("get", self._lib.rtn_get, object_id,
                          ctypes.byref(ptr), ctypes.byref(ln)), "get")
        arr = np.ctypeslib.as_array(ptr, shape=(ln.value,))
        return memoryview(arr)

    def contains(self, object_id: int) -> bool:
        return bool(self._call("contains", self._lib.rtn_contains,
                               object_id))

    def delete(self, object_id: int):
        _check(self._call("delete", self._lib.rtn_delete, object_id),
               "delete")

    def stats(self) -> dict:
        lib = self._lib
        return self._call("stats", lambda h: {
            "capacity": lib.rtn_store_capacity(h),
            "used": lib.rtn_store_used(h),
            "num_objects": lib.rtn_store_num_objects(h),
        })

    # ----------------------------------------------------- mutable objects
    def mo_create(self, object_id: int, max_size: int,
                  num_readers: int = 1):
        _check(self._call("mo_create", self._lib.rtn_mo_create, object_id,
                          max_size, num_readers), "mo_create")

    def mo_write(self, object_id: int, data: bytes,
                 timeout_s: float = 60.0):
        _check(self._call("mo_write", self._lib.rtn_mo_write, object_id,
                          data, len(data), int(timeout_s * 1000)),
               "mo_write")

    def mo_read(self, object_id: int, last_seen: int, max_size: int,
                timeout_s: float = 60.0) -> (bytes, int):
        buf = ctypes.create_string_buffer(max_size)
        ln = ctypes.c_uint64()
        ver = ctypes.c_uint64()
        _check(self._call(
            "mo_read", self._lib.rtn_mo_read, object_id, last_seen, buf,
            max_size, ctypes.byref(ln), ctypes.byref(ver),
            int(timeout_s * 1000)), "mo_read")
        return buf.raw[:ln.value], ver.value

    def mo_close(self, object_id: int):
        _check(self._call("mo_close", self._lib.rtn_mo_close, object_id),
               "mo_close")

    def mo_destroy(self, object_id: int):
        """Close + reclaim the payload arena (owner teardown path)."""
        _check(self._call("mo_destroy", self._lib.rtn_mo_destroy,
                          object_id), "mo_destroy")


class NativeMutableChannel:
    """Channel API over a native mutable object — the cross-process
    SharedMemoryChannel (channels/channel.py Channel protocol)."""

    _COUNTER = [0]

    def __init__(self, store: NativeObjectStore, object_id: Optional[int]
                 = None, max_size: int = 1 << 20, num_readers: int = 1,
                 create: bool = True):
        self._store = store
        if object_id is None:
            NativeMutableChannel._COUNTER[0] += 1
            object_id = (os.getpid() << 20) | NativeMutableChannel._COUNTER[0]
        self.object_id = object_id
        self.max_size = max_size
        if create:
            store.mo_create(object_id, max_size, num_readers)
        self._last_seen = [0] * num_readers

    def write(self, value, timeout: Optional[float] = None):
        import pickle

        data = pickle.dumps(value, protocol=5)
        try:
            self._store.mo_write(self.object_id, data,
                                 timeout_s=timeout if timeout else 60.0)
        except NativeError as e:
            # A destroyed channel or a closed store is closed to peers.
            if e.code in (-2, _CLOSED):
                raise ChannelError("channel destroyed") from None
            raise

    def read(self, reader_id: int = 0, timeout: Optional[float] = None):
        import pickle

        try:
            data, ver = self._store.mo_read(
                self.object_id, self._last_seen[reader_id], self.max_size,
                timeout_s=timeout if timeout else 60.0)
        except NativeError as e:
            # A destroyed channel or a closed store is closed to peers.
            if e.code in (-2, _CLOSED):
                raise ChannelError("channel destroyed") from None
            raise
        self._last_seen[reader_id] = ver
        return pickle.loads(data)

    def close(self):
        """Signal EOF; committed data stays readable (drain semantics)."""
        try:
            self._store.mo_close(self.object_id)
        except NativeError:
            pass

    def destroy(self):
        """Close + reclaim the payload arena — only when no peer can still
        drain (e.g. the worker process on the other end is dead)."""
        try:
            self._store.mo_destroy(self.object_id)
        except NativeError:
            pass


class NativeTaskQueue:
    """Dependency-tracking ready queue (the C++ scheduler hot loop)."""

    def __init__(self, max_tasks: int, max_edges: int):
        self._lib = load_native()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self._q = self._lib.rtn_tq_create(max_tasks, max_edges)
        self._sealed = False

    def add_task(self, task_id: int):
        if self._lib.rtn_tq_add_task(self._q, task_id) != 0:
            raise ValueError(f"bad task id {task_id} (or sealed)")

    def add_edge(self, src: int, dst: int):
        if self._lib.rtn_tq_add_edge(self._q, src, dst) != 0:
            raise ValueError(f"bad edge {src}->{dst} (or sealed/full)")

    def seal(self):
        if self._lib.rtn_tq_seal(self._q) != 0:
            raise RuntimeError("already sealed")
        self._sealed = True

    def complete(self, task_ids: List[int]):
        arr = (ctypes.c_uint32 * len(task_ids))(*task_ids)
        self._lib.rtn_tq_complete(self._q, arr, len(task_ids))

    def pop_wave(self, max_tasks: int = 1024,
                 timeout_s: float = 1.0) -> List[int]:
        out = (ctypes.c_uint32 * max_tasks)()
        n = self._lib.rtn_tq_pop_wave(self._q, out, max_tasks,
                                      int(timeout_s * 1000))
        return list(out[:n])

    @property
    def num_done(self) -> int:
        return self._lib.rtn_tq_num_done(self._q)

    @property
    def num_tasks(self) -> int:
        return self._lib.rtn_tq_num_tasks(self._q)

    def __del__(self):
        try:
            if getattr(self, "_q", None):
                self._lib.rtn_tq_destroy(self._q)
                self._q = None
        except Exception:  # noqa: BLE001
            pass


class NativeDynQueue:
    """Incremental dependency queue (the live scheduler's native hot loop).

    Handles are opaque uint64s with an embedded generation so completed
    slots recycle safely; ``add_dep`` against an already-completed producer
    is a no-op (the dependency is satisfied).
    """

    def __init__(self, max_tasks: int = 1 << 16, max_edges: int = 1 << 18):
        self._lib = load_native()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self._q = self._lib.rtn_dq_create(max_tasks, max_edges)

    def alloc(self) -> int:
        h = self._lib.rtn_dq_alloc(self._q)
        if h == 0:
            raise MemoryError("dynamic task queue is full")
        return h

    def add_dep(self, task: int, dep: int):
        rc = self._lib.rtn_dq_add_dep(self._q, task, dep)
        if rc == -3:
            raise MemoryError("dynamic task queue edge table is full")
        if rc != 0:
            raise ValueError(f"bad task handle {task:#x}")

    def commit(self, task: int):
        if self._lib.rtn_dq_commit(self._q, task) != 0:
            raise ValueError(f"bad task handle {task:#x}")

    def complete(self, task: int):
        if self._lib.rtn_dq_complete(self._q, task) != 0:
            raise ValueError(f"bad/uncommitted task handle {task:#x}")

    def abort(self, task: int):
        """Abandon a task that never ran (registration unwind); its slot is
        recycled and edges into it go stale via the generation tag."""
        if self._lib.rtn_dq_abort(self._q, task) != 0:
            raise ValueError(f"bad task handle {task:#x}")

    def pop(self, max_tasks: int = 1024, timeout_s: float = 0.2) -> List[int]:
        out = (ctypes.c_uint64 * max_tasks)()
        n = self._lib.rtn_dq_pop(self._q, out, max_tasks,
                                 int(timeout_s * 1000))
        return list(out[:n])

    def wake(self):
        self._lib.rtn_dq_wake(self._q)

    @property
    def num_pending(self) -> int:
        return self._lib.rtn_dq_num_pending(self._q)

    @property
    def num_done(self) -> int:
        return self._lib.rtn_dq_num_done(self._q)

    def __del__(self):
        try:
            if getattr(self, "_q", None):
                self._lib.rtn_dq_destroy(self._q)
                self._q = None
        except Exception:  # noqa: BLE001
            pass
