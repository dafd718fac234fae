"""The one place that reads the backend's name.

Every kernel in ``ops/`` and every dispatch that depends on a kernel
asks :func:`on_cpu`: the CPU backend is where the tests run, so a Pallas
kernel is interpreted there, the model keeps its dense ``jnp`` attention
(interpreting a kernel in every layer would take the tests minutes), and
buffer donation is skipped (the CPU backend cannot honour it and warns).
On every other backend the kernel is compiled for the device or the
call raises — no accelerator is ever served by the interpreter or by a
silent dense stand-in because its platform string was unexpected.
"""

from __future__ import annotations

import os
import threading
from collections import deque

import jax


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# The build log: one record per program this process built, closed by
# its backend span, which JAX fires for a compile and for a load from the
# persistent cache alike; the record's ``cache`` says which. Fed by three
# kinds of ``jax.monitoring`` listener and by nothing else, so between
# builds it costs nothing: a measurement window reads the count before
# and after, and it should not move. What a record holds and how to read
# it: ``ray_tpu/util/profiling.py``.
LOG_RECORDS = 4096
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _union_s(spans: list) -> float:
    """Seconds covered by ``(start, end)`` spans; one inside another
    counts once."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class BuildLog:
    """The records of the last ``LOG_RECORDS`` builds and the totals of
    all of them. A thread's trace and lowering spans and its cache events
    wait for that thread's next backend span, which closes the record; a
    ``.lower()`` that is never compiled waits for the thread's next
    build."""

    def __init__(self, maxlen: int = LOG_RECORDS):
        self._records = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._threads = threading.local()
        self._builds: dict = {}          # name -> records closed under it
        self._totals = {"compilations": 0, "compile_seconds": 0.0,
                        "trace_lower_seconds": 0.0, "cache_hits": 0,
                        "cache_misses": 0, "rebuilt": 0}

    def _pending(self) -> dict:
        p = getattr(self._threads, "pending", None)
        if p is None:
            # bounded too: a thread may trace for ever and never build
            p = self._threads.pending = {
                "trace": deque(maxlen=LOG_RECORDS),
                "lower": deque(maxlen=LOG_RECORDS),
                "cache": "off", "retrieval_s": 0.0}
        return p

    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self._pending()["cache"] = "hit"
        elif event in (_CACHE_ASKED, _CACHE_MISS):
            # asked and not hit: a miss, whether or not the compile is
            # long and large enough to be written back (JAX fires
            # ``cache_misses`` only where it writes)
            p = self._pending()
            if p["cache"] == "off":
                p["cache"] = "miss"

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _CACHE_RETRIEVAL:
            self._pending()["retrieval_s"] += duration

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_kw) -> None:
        if event == _TRACE:
            self._pending()["trace"].append((start, end))
        elif event == _LOWER:
            self._pending()["lower"].append((start, end))
        elif event == _BACKEND:
            p = self._pending()
            del self._threads.pending
            t0 = min([start] + [s for s, _ in (*p["trace"], *p["lower"])])
            self._close(fun_name, {
                "t0": t0, "t1": end, "trace_s": _union_s(p["trace"]),
                "lower_s": _union_s(p["lower"]), "backend_s": end - start,
                "cache": p["cache"], "retrieval_s": p["retrieval_s"],
                "thread": threading.get_ident()})

    def _close(self, name: str, fields: dict) -> None:
        with self._lock:
            t = self._totals
            t["compilations"] += 1
            record = {"seq": t["compilations"], "name": name, **fields}
            built = self._builds[name] = self._builds.get(name, 0) + 1
            if built > 1:
                record["rebuilt"] = built
                t["rebuilt"] += 1
            t["compile_seconds"] += record["backend_s"]
            t["trace_lower_seconds"] += record["trace_s"] + record["lower_s"]
            t["cache_hits"] += record["cache"] == "hit"
            t["cache_misses"] += record["cache"] == "miss"
            self._records.append(record)

    def totals(self) -> dict:
        """Counts and seconds over every record closed, kept or not."""
        with self._lock:
            return dict(self._totals)

    def records(self, since_seq: int = 0) -> list:
        """Copies of the kept records closed after ``since_seq``, oldest
        first."""
        with self._lock:
            return [dict(r) for r in self._records if r["seq"] > since_seq]


_LOG = BuildLog()
build_log = _LOG.records
jax.monitoring.register_event_listener(_LOG.on_event)
jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
jax.monitoring.register_event_time_span_listener(_LOG.on_span)


def device_info() -> dict:
    """Platform, device kind and device count of THIS process, as JAX
    reports them. Carried by ``engine.stats()`` and the train session
    context so a caller in another process can refuse a CPU result.
    ``visible_chips`` is the host chip a one-chip worker was shown (it
    sees that chip as its device 0), None when it sees the whole host;
    ``compilations`` / ``compile_seconds`` are this process's build log
    so far (records closed, seconds in their backend spans) and with them
    ``trace_lower_seconds``, ``cache_hits``, ``cache_misses`` and
    ``rebuilt`` (records under a name built before); the byte counts are
    ``memory_stats()`` of each local device."""
    devices = jax.devices()
    memory = [d.memory_stats() or {} for d in devices]  # None on the CPU
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax_version": jax.__version__,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pid": os.getpid(),
        **{k: round(v, 3) if isinstance(v, float) else v
           for k, v in _LOG.totals().items()},
        "device_bytes_in_use": [m.get("bytes_in_use") for m in memory],
        "device_peak_bytes": [m.get("peak_bytes_in_use") for m in memory],
    }
