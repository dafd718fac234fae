"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.moe_route``, forward and
backward: the expert layers' norm, float32 router product over 512, sigmoid
scores, top-22 of scores plus bias, gates, the sort of 90,112 pairs and the
group sizes (``parallel/moe.py:route``); models/transformer.py. The family's
join places an instruction (perfbench/families/nemotron_h/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no
matmul decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_route") if spent else None
