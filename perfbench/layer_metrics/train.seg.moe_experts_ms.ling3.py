"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.moe_experts``, forward and
backward: the six expert layers' held experts: the gather of the pairs routed
here, the grouped products, the row-wise stages, the weighted scatter-add and
the residual add (parallel/moe.py: ``held_experts``); models/transformer.py.
The family's join places an instruction (perfbench/families/ling3/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no matmul
decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_experts") if spent else None
