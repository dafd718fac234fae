"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.mlp``, forward and backward:
the leading dense layer's feed-forward: its norm, SwiGLU at the dense width and the residual add (``_mlp_residual``); an expert layer's feed-forward is not under it; models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.mlp")
