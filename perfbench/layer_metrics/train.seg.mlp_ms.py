"""Milliseconds of device self time a traced step spends in operations whose
outermost segment is ``seg.mlp``, forward and backward:
the MLP norm, SwiGLU and the residual add (``_mlp_residual``); models/transformer.py. perfbench/segments.py joins
the trace's instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.mlp")
