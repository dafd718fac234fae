"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.moe_route``, forward and backward:
an expert layer's routing: the feed-forward norm, the float32 router product, sigmoid scores, top-k over scores plus bias, the gates, the sort of the pairs and the group sizes (``_moe_residual``, ``parallel/moe.py:route``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.moe_route")
