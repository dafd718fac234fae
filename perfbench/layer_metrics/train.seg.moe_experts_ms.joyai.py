"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.moe_experts``, forward and backward:
the four stack expert layers' held experts: the gather of the pairs routed
here, the grouped products, the row-wise stages, the weighted scatter-add and
the residual add (parallel/moe.py: ``held_experts``); models/transformer.py.
The module's are ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_experts") if spent else None
