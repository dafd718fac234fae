"""Device profiling (reference role: ray.timeline's device-side sibling —
upstream integrates torch/NSight profilers; here the XLA profiler).

``profile_trace`` captures an XLA/xplane trace (TensorBoard-loadable) of
everything the device executes inside the block — compiled-DAG waves,
train steps, collectives — complementing the host-side task timeline
(``ray_tpu.timeline``). ``annotate`` nests named spans into that trace so
framework phases (a wave, a pipeline stage) are attributable in the
device view.

Division of labor between the two profilers:

- **This module (device)** — the XLA profiler records what the
  ACCELERATOR executed: per-op device time, fusion boundaries, HBM
  traffic, host↔device transfers. Heavyweight capture, bounded
  windows, explicit ``with profile_trace(...)`` blocks, output is
  xplane protobufs for TensorBoard's profile plugin.
- **``_private/flight.py`` (host)** — the flight recorder's sampling
  profiler records what the PYTHON HOST PLANE was doing: folded
  wall-clock stacks of every thread (scheduler, transport, GIL hogs),
  always-on under ``RAY_TPU_PROFILE``, collapsed/speedscope output.
  A slow step shows up here when the host is the bottleneck and in
  the xplane trace when the device is.

**What a capture names.** The model names its own device work, so a
``profile_trace`` of any step, training or serving, shows in the trace
viewer's operation details (the ``op_name`` of each XLA operation) which
part of the model the operation is: one of ``SEGMENTS`` (``seg.embed``,
``seg.attn_proj``, ``seg.attn_core``, ``seg.mlp``, ``seg.head_loss``, and
of the further layer kinds ``seg.conv``, ``seg.moe_route``, ``seg.moe_experts``,
``seg.moe_shared``, ``seg.kda_proj``, ``seg.kda_core``,
``seg.mamba_proj``, ``seg.mamba_core``, ``seg.moe_latent``, ``seg.exit``
round a looped stack's exit gates and the weighing of its passes' losses,
and ``seg.mtp`` round a multi-token-prediction module whole;
the outermost one on the path is the operation's segment, ``norm`` and
``rope`` are finer scopes inside), and on the flash kernels one of
``KERNELS`` (``flash_fwd``, ``flash_fwd_grouped``, ``flash_bwd_dq``,
``flash_bwd_dkv``), which is also the kernel instruction's own name
(``%flash_fwd.6``); on an expert layer's grouped products ``moe_gmm``
or ``moe_tgmm``, on its passes over the rows routed here
``moe_gather_rows``, ``moe_map_rows`` or ``moe_scatter_rows``.
``transpose(jvp(...))`` on the path marks the backward
pass; an operation with names and no segment is the optimizer's. The
scopes are written in ``models/transformer.py`` and ``ops/``; they exist
while a program is traced and cost nothing when it runs.

**The build log.** The program keeps one record for each program this
process built (``ops/backend.py``, fed by ``jax.monitoring``'s listeners
and nothing else), closed by the program's backend span, which JAX fires
for a compile and for a load from the persistent cache alike. A record
holds ``seq`` (the order of closing, from 1), ``name`` (the backend span's
``fun_name``: ``jit(step)``), ``t0`` and ``t1`` (epoch seconds: the start
of the first span that waited for it to the backend span's end),
``trace_s`` (the length of the union of the thread's
``/jax/core/compile/jaxpr_trace_duration`` spans since its previous
record: an inner ``jit``'s trace lies inside the outer one's and counts
once), ``lower_s`` (``jaxpr_to_mlir_module_duration``), ``backend_s``
(``backend_compile_duration``), ``cache`` (``"hit"`` where
``/jax/compilation_cache/cache_hits`` fired inside the backend span,
``"miss"`` where the cache was asked, ``compile_requests_use_cache`` or
``cache_misses``, and had nothing, ``"off"`` where it was not asked),
``retrieval_s`` (``cache_retrieval_time_sec``, the read alone, on a hit),
``thread`` (the building thread's ident; a thread's trace and lowering
spans wait for that thread's next backend span, so an ahead-of-time
``.lower()`` with no ``.compile()`` is counted into the thread's next
build) and, where the log has closed a record of that ``name`` before,
``rebuilt``: n for the n-th build of the name.

It is always on and has no switch, because nothing fires it outside a
build: between builds it costs nothing, and a build pays three listeners a
few dictionary operations for each of its events. It keeps the last 4,096
records; the totals that ``device_info()`` reports count every record
closed.

An operator asks it two questions. *What did this replica's cold start
spend?* ``engine.stats()`` / ``train.get_context().get_device_info()``
carry the totals of the process that holds the model: ``compilations``
(records closed), ``compile_seconds`` (their backend spans),
``trace_lower_seconds`` (paid from a warm cache as from an empty one),
``cache_hits`` and ``cache_misses``; in that process ``build_log()`` gives
the records, largest ``backend_s`` first to find what to ship in the
cache. *Which program was built again after warm-up?* Note ``compilations``
(a record's ``seq``) when warm-up ends; later, ``rebuilt`` in the totals
having moved says that a name was built a second time, and
``build_log(since_seq=<that count>)`` names it, with the clock of each
build: a step that meets a new shape shows here by name.

**A capture's clock.** A capture's times count from the capture's start
and every record the program keeps itself (the build log, ``_private/
tracing.py``'s spans) is on ``time.time()``. ``profile_trace`` writes the
epoch of the capture's start into ``<logdir>/clock.json``, and
``host_spans(logdir)`` puts the program's records that overlap the capture
on the capture's own axis. Measured on the v5e (PR 43, five captures, a
``TraceAnnotation`` opened at a known epoch and found again in the
capture): the capture's zero stands 22 to 44 microseconds after the epoch
in ``clock.json`` (``start_trace`` itself takes 40 to 46 ms; on a CPU
backend 18 to 56 microseconds and 0.1 ms). That is under a millisecond,
so ``host_spans`` applies no correction.

The two meet in the debug-bundle plane: every ``profile_trace``
capture registers its logdir with the flight recorder, so a bundle
(``ray_tpu.debug_dump()``) lists the device-trace artifacts produced
this session next to the host-side stacks.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Iterator, List, Optional, Tuple

CLOCK_FILE = "clock.json"

# The names the model gives its device work: ``jax.named_scope``s in
# ``models/transformer.py`` and ``ops/paged_attention.py``. A reader
# gives an operation to the OUTERMOST of these on its ``op_name`` path;
# the ``seg.`` prefix is one no JAX primitive or transform produces.
SEGMENTS = ("seg.embed", "seg.attn_proj", "seg.attn_core", "seg.mlp",
            "seg.head_loss",
            # the further layer kinds (models/transformer.py): the gated
            # short convolution with its norm and residual; an expert
            # layer's norm, router, top-k, gates and sort; its gather,
            # grouped products, weighted scatter-add and residual
            "seg.conv", "seg.moe_route", "seg.moe_experts",
            # a KDA layer's norm, projections, taps, gates, output norm,
            # ``W_o`` and residual; its chunked scan (``ops/kda.py``); an
            # expert layer's shared expert
            "seg.kda_proj", "seg.kda_core", "seg.moe_shared",
            # a Mamba-2 layer's norm, ``W_in``, taps, step, gated norm,
            # ``W_out`` and residual; its chunked scan (``ops/ssd.py``);
            # the projections down to and up from the latent width the
            # routed experts work at
            "seg.mamba_proj", "seg.mamba_core", "seg.moe_latent",
            # a looped stack's exit gates, their distribution over the
            # passes, its entropy and the passes' losses weighed by it
            "seg.exit",
            # a multi-token-prediction module whole, outermost on its path:
            # its two norms, the join, its layer, its head pass and loss
            "seg.mtp")
# The Pallas kernels of ``ops/flash_attention.py``: each one's ``name=``
# and the scope around its call; and of ``ops/grouped_matmul.py``, its
# two kernels named the same way; and of ``ops/moe_rows.py``: the scope
# around each pass over the sorted rows
# an expert layer works on (the row-wise one a Pallas call of that name,
# the gather and the scatter-add each a loop around XLA's own). The
# chunked scans of ``ops/kda.py`` and ``ops/ssd.py`` are plain XLA and have
# no name here: each is all of its segment (``seg.kda_core``,
# ``seg.mamba_core``). ``ops/kda.py``'s backward pass is a ``custom_vjp``'s
# and carries the segment as the flash kernels' does; it reads the chunks'
# inverses and states the forward kept (42 MB a layer at 4096 tokens of 8
# heads of 128) and makes no forward of its own. ``ops/ssd.py``'s is still
# the forward made again under ``jax.checkpoint`` and transposed. The
# table look-up's (``models/transformer.py:_embed``) is a ``custom_vjp``'s
# too and writes ``seg.embed`` itself: the rows' cotangents summed at
# their ids into the table's float32 gradient.
KERNELS = ("flash_fwd", "flash_fwd_grouped", "flash_bwd_dq",
           "flash_bwd_dkv", "moe_gmm", "moe_tgmm",
           "moe_gather_rows", "moe_map_rows", "moe_scatter_rows")


@contextlib.contextmanager
def profile_trace(logdir: str,
                  host_tracer_level: Optional[int] = None) -> Iterator[str]:
    """Capture an xplane device+host trace into ``logdir``.

    View with TensorBoard's profile plugin, or post-process the
    ``*.xplane.pb`` files. Works on every backend (CPU tests included).
    """
    import jax

    from ray_tpu.ops import backend  # noqa: F401  the build log listens

    os.makedirs(logdir, exist_ok=True)
    clock = {"epoch_ns_at_start": time.time_ns()}
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        clock["epoch_ns_at_stop"] = time.time_ns()
        jax.profiler.stop_trace()
        with open(os.path.join(logdir, CLOCK_FILE), "w") as f:
            json.dump(clock, f)
        # Register the capture with the flight-recorder bundle plane:
        # a debug bundle lists every device-trace dir this session
        # produced (no-op while the recorder is disarmed).
        from ray_tpu._private import flight

        flight.note_artifact(os.path.abspath(logdir))


def build_log(since_seq: int = 0) -> List[dict]:
    """Copies of the build log's records closed after ``since_seq``
    (``device_info()["compilations"]`` at some earlier moment), oldest
    first; the module docstring says what a record holds."""
    from ray_tpu.ops import backend

    return backend.build_log(since_seq)


def host_spans(logdir: str) -> List[Tuple[str, int, int]]:
    """What the program recorded on the host while the capture in
    ``logdir`` ran, as ``(name, start_ns, duration_ns)`` on the capture's
    own axis, sorted by start: the build log's records (``build:<name>``,
    from ``t0`` to ``t1``) and, where ``RAY_TPU_TRACE`` armed the tracer,
    its ``local_spans()``. A span that began before the capture starts
    below zero. Only the process that made the capture has its records."""
    from ray_tpu._private import tracing

    with open(os.path.join(logdir, CLOCK_FILE)) as f:
        clock = json.load(f)
    zero, stop = clock["epoch_ns_at_start"], clock["epoch_ns_at_stop"]
    spans = [("build:" + r["name"], r["t0"], r["t1"]) for r in build_log()]
    spans += [(s["name"], s["t0"], s["t0"] + s["dur"])
              for s in tracing.local_spans()]
    on_axis = [(name, int(t0 * 1e9) - zero, int((t1 - t0) * 1e9))
               for name, t0, t1 in spans]
    return sorted((s for s in on_axis
                   if s[1] < stop - zero and s[1] + s[2] > 0),
                  key=lambda s: s[1])


def annotate(name: str):
    """Named span inside an active trace (TraceAnnotation passthrough)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def trace_files(logdir: str):
    """The xplane protobuf files a capture produced under ``logdir``."""
    out = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                out.append(os.path.join(root, f))
    return sorted(out)
