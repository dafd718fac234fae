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
``seg.mamba_proj``, ``seg.mamba_core``, ``seg.moe_latent``;
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

The two meet in the debug-bundle plane: every ``profile_trace``
capture registers its logdir with the flight recorder, so a bundle
(``ray_tpu.debug_dump()``) lists the device-trace artifacts produced
this session next to the host-side stacks.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

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
            "seg.mamba_proj", "seg.mamba_core", "seg.moe_latent")
# The Pallas kernels of ``ops/flash_attention.py``: each one's ``name=``
# and the scope around its call; and of ``ops/grouped_matmul.py``, its
# two kernels named the same way; and of ``ops/moe_rows.py``: the scope
# around each pass over the sorted rows
# an expert layer works on (the row-wise one a Pallas call of that name,
# the gather and the scatter-add each a loop around XLA's own). The
# chunked scans of ``ops/kda.py`` and ``ops/ssd.py`` are plain XLA and have
# no name here: each is all of its segment (``seg.kda_core``,
# ``seg.mamba_core``).
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

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
        # Register the capture with the flight-recorder bundle plane:
        # a debug bundle lists every device-trace dir this session
        # produced (no-op while the recorder is disarmed).
        from ray_tpu._private import flight

        flight.note_artifact(os.path.abspath(logdir))


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
