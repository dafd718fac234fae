"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.embed``, forward and backward:
the table's gather, backward the rows' cotangents summed into the
float32 table (``_embed``): once a step, before the first pass; models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. The backward pass's recomputation of the
checkpointed layers and heads keeps its segment and counts here too
(train.recompute_ms.ouro reads it apart)."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.embed")
