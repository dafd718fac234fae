"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.moe_shared``, forward and backward:
the four stack expert layers' shared expert: one SwiGLU of width 768 that
every token goes through, and its residual add (``_moe_residual``);
models/transformer.py. The module's is ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_shared") if spent else None
