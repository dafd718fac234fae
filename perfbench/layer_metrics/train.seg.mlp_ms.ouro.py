"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.mlp``, forward and backward:
the MLP norm, SwiGLU at 5632, its sandwich norm and the residual add
(``_mlp_residual``), in each of the 32 layer applications; models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. The backward pass's recomputation of the
checkpointed layers and heads keeps its segment and counts here too
(train.recompute_ms.ouro reads it apart)."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.mlp")
