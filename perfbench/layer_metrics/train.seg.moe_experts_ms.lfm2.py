"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.moe_experts``, forward and backward:
the held experts' part of an expert layer: the gather of the sorted pairs' rows, the three grouped products (``moe_gmm``, ``moe_tgmm``), the weighted scatter-add and the residual add (``_moe_residual``, ``parallel/moe.py:held_experts``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.moe_experts")
