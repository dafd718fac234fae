"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.moe_shared``, forward and backward:
an expert layer's shared expert: one SwiGLU of width 768 that every token goes
through, and its residual add (``_moe_residual``); models/transformer.py. The
family's join places an instruction (perfbench/families/ling3/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no matmul
decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_shared") if spent else None
