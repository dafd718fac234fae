"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.conv``, forward and backward:
a conv layer's sequence operator: the operator norm, ``W_in``, both gates, the three causal taps, ``W_out`` and the residual add (``_conv_residual``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.conv")
