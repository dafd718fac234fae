"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.head_loss``, forward and backward:
the final norm, the tied head's matmul against the table's slice, the f32 logits and the loss (``_final_logits``, ``_next_token_nll``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.head_loss")
