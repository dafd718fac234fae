"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.attn_core``, forward and backward:
the transposes round the three flash kernels at 16 heads of 128
(``_attention_dense``), in each of the 32 layer applications; models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. The backward pass's recomputation of the
checkpointed layers and heads keeps its segment and counts here too
(train.recompute_ms.ouro reads it apart)."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.attn_core")
