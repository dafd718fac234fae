"""Build + load the native library (ctypes, no pybind11 — per environment:
Python↔C++ binding via ctypes over a C ABI)."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_SOURCES = ["object_store.cc", "task_queue.cc"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _src_dir() -> str:
    return os.path.dirname(os.path.abspath(__file__))


def _cache_dir() -> str:
    from ray_tpu._private.config import GlobalConfig

    d = GlobalConfig.native_cache or os.path.join(
        os.path.expanduser("~"), ".cache", "ray_tpu")
    os.makedirs(d, exist_ok=True)
    return d


def _build() -> Optional[str]:
    srcs = [os.path.join(_src_dir(), s) for s in _SOURCES]
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    so_path = os.path.join(
        _cache_dir(), f"libray_tpu_native-{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread", "-std=c++17",
           *srcs, "-o", so_path + ".tmp", "-lrt"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired):
        return None
    os.replace(so_path + ".tmp", so_path)
    return so_path


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, u32, i64, i32 = (ctypes.c_uint64, ctypes.c_uint32,
                          ctypes.c_int64, ctypes.c_int32)
    p = ctypes.c_void_p
    u8p = ctypes.POINTER(ctypes.c_uint8)

    lib.rtn_store_create.restype = p
    lib.rtn_store_create.argtypes = [ctypes.c_char_p, u64, u32]
    lib.rtn_store_open.restype = p
    lib.rtn_store_open.argtypes = [ctypes.c_char_p]
    lib.rtn_store_close.argtypes = [p]
    lib.rtn_store_unlink.argtypes = [p]
    lib.rtn_store_capacity.restype = u64
    lib.rtn_store_capacity.argtypes = [p]
    lib.rtn_store_used.restype = u64
    lib.rtn_store_used.argtypes = [p]
    lib.rtn_store_num_objects.restype = u64
    lib.rtn_store_num_objects.argtypes = [p]
    lib.rtn_put.restype = i32
    lib.rtn_put.argtypes = [p, u64, ctypes.c_char_p, u64]
    lib.rtn_get.restype = i32
    lib.rtn_get.argtypes = [p, u64, ctypes.POINTER(u8p),
                            ctypes.POINTER(u64)]
    lib.rtn_contains.restype = i32
    lib.rtn_contains.argtypes = [p, u64]
    lib.rtn_delete.restype = i32
    lib.rtn_delete.argtypes = [p, u64]
    lib.rtn_mo_create.restype = i32
    lib.rtn_mo_create.argtypes = [p, u64, u64, u32]
    lib.rtn_mo_write.restype = i32
    lib.rtn_mo_write.argtypes = [p, u64, ctypes.c_char_p, u64, i64]
    lib.rtn_mo_read.restype = i32
    lib.rtn_mo_read.argtypes = [p, u64, u64, ctypes.c_char_p, u64,
                                ctypes.POINTER(u64), ctypes.POINTER(u64),
                                i64]
    lib.rtn_mo_close.restype = i32
    lib.rtn_mo_close.argtypes = [p, u64]
    lib.rtn_mo_destroy.restype = i32
    lib.rtn_mo_destroy.argtypes = [p, u64]

    lib.rtn_tq_create.restype = p
    lib.rtn_tq_create.argtypes = [u32, u32]
    lib.rtn_tq_destroy.argtypes = [p]
    lib.rtn_tq_add_task.restype = i32
    lib.rtn_tq_add_task.argtypes = [p, u32]
    lib.rtn_tq_add_edge.restype = i32
    lib.rtn_tq_add_edge.argtypes = [p, u32, u32]
    lib.rtn_tq_seal.restype = i32
    lib.rtn_tq_seal.argtypes = [p]
    lib.rtn_tq_complete.restype = i32
    lib.rtn_tq_complete.argtypes = [p, ctypes.POINTER(u32), u32]
    lib.rtn_tq_pop_wave.restype = i32
    lib.rtn_tq_pop_wave.argtypes = [p, ctypes.POINTER(u32), u32, i64]
    lib.rtn_tq_num_done.restype = u32
    lib.rtn_tq_num_done.argtypes = [p]
    lib.rtn_tq_num_tasks.restype = u32
    lib.rtn_tq_num_tasks.argtypes = [p]

    lib.rtn_dq_create.restype = p
    lib.rtn_dq_create.argtypes = [u32, u32]
    lib.rtn_dq_destroy.argtypes = [p]
    lib.rtn_dq_alloc.restype = u64
    lib.rtn_dq_alloc.argtypes = [p]
    lib.rtn_dq_add_dep.restype = i32
    lib.rtn_dq_add_dep.argtypes = [p, u64, u64]
    lib.rtn_dq_commit.restype = i32
    lib.rtn_dq_commit.argtypes = [p, u64]
    lib.rtn_dq_complete.restype = i32
    lib.rtn_dq_complete.argtypes = [p, u64]
    lib.rtn_dq_abort.restype = i32
    lib.rtn_dq_abort.argtypes = [p, u64]
    lib.rtn_dq_pop.restype = i32
    lib.rtn_dq_pop.argtypes = [p, ctypes.POINTER(u64), u32, i64]
    lib.rtn_dq_wake.argtypes = [p]
    lib.rtn_dq_num_pending.restype = u64
    lib.rtn_dq_num_pending.argtypes = [p]
    lib.rtn_dq_num_done.restype = u64
    lib.rtn_dq_num_done.argtypes = [p]
    return lib


def load_native() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        so = _build()
        if so is None:
            _load_failed = True
            return None
        try:
            _lib = _bind(ctypes.CDLL(so))
        except OSError:
            _load_failed = True
            return None
        return _lib


def native_available() -> bool:
    return load_native() is not None
