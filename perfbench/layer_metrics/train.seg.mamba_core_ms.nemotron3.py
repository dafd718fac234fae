"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.mamba_core``, forward and
backward: the Mamba-2 layers' selective scan: ``ssd_chunk`` of ops/ssd.py (a
chunk's scores, the decay's segment sums, the chunks' states and the
``lax.scan`` that carries them), made again once in the backward pass;
models/transformer.py. The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.mamba_core") if spent else None
