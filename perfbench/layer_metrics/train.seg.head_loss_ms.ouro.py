"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations whose outermost segment is ``seg.head_loss``, forward and backward:
the final norm after each pass, the ``lm_head`` matmul, the f32 logits
and the next-token loss of each of the four passes (``_passes``,
``_exit_loss``); models/transformer.py.
perfbench/segments.py joins the trace's instruction names to the scopes
through the compiled step. The backward pass's recomputation of the
checkpointed layers and heads keeps its segment and counts here too
(train.recompute_ms.ouro reads it apart)."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.head_loss")
