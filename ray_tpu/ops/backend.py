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

import jax


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# Compilations of this process, counted from JAX's own monitoring events
# (one per backend compile or persistent-cache load). A measurement
# window reads the count before and after: it should not move.
_compiles = {"count": 0, "seconds": 0.0}


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["count"] += 1
        _compiles["seconds"] += duration


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def device_info() -> dict:
    """Platform, device kind and device count of THIS process, as JAX
    reports them. Carried by ``engine.stats()`` and the train session
    context so a caller in another process can refuse a CPU result.
    ``visible_chips`` is the host chip a one-chip worker was shown (it
    sees that chip as its device 0), None when it sees the whole host;
    ``compilations`` / ``compile_seconds`` are this process's so far;
    the byte counts are ``memory_stats()`` of each local device."""
    devices = jax.devices()
    memory = [d.memory_stats() or {} for d in devices]  # None on the CPU
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax_version": jax.__version__,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pid": os.getpid(),
        "compilations": _compiles["count"],
        "compile_seconds": round(_compiles["seconds"], 3),
        "device_bytes_in_use": [m.get("bytes_in_use") for m in memory],
        "device_peak_bytes": [m.get("peak_bytes_in_use") for m in memory],
    }
