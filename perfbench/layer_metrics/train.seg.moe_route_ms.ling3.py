"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.moe_route``, forward and backward:
the six expert layers' routing: the feed-forward norm, the f32 router product
512 wide, the sigmoid scores, the groups' scores and the 4 kept, the top 8, the
gates, the sort of the 32,768 pairs (``_moe_residual``, parallel/moe.py:
``route``); models/transformer.py. The family's join places an instruction
(perfbench/families/ling3/join.py: perfbench/segments.py's join through the
compiled step, and a fusion no matmul decides to the class of two thirds of its
operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_route") if spent else None
