"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.kda_core``, forward and backward:
the KDA layers' recurrence: ``kda_chunk`` of ops/kda.py (the chunks' score
matrices, the triangular solve, the scan over the chunks that carries the
state), recomputed once in the backward pass; models/transformer.py. The
family's join places an instruction (perfbench/families/ling3/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no matmul
decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.kda_core") if spent else None
