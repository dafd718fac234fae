"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.attn_proj``, forward and backward:
the attention norm, the q/k/v projections with rope, the output projection,
its sandwich norm and the residual add (``_project_qkv``, ``_attn_out``), in
each of the 32 layer applications (8 layers, 4 passes); models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. The backward pass's recomputation of the
checkpointed layers and heads keeps its segment and counts here too
(train.recompute_ms.ouro reads it apart)."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.attn_proj")
