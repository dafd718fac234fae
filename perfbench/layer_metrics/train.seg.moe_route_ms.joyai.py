"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.moe_route``, forward and backward:
the four stack expert layers' routing: the feed-forward norm, the f32 router
product 256 wide, the sigmoid scores, the top 8, the gates, the sort of the
32,768 pairs (``_moe_residual``, parallel/moe.py: ``route``);
models/transformer.py. The module's router is ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_route") if spent else None
