"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.head_loss``, forward and backward:
the next-token loss's head pass: the final norm, the untied head of 16,160
columns, the f32 logits, log-softmax, pick and mean (``_final_logits``,
``_next_token_nll``); models/transformer.py. The module's pass through the
same head is ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.head_loss") if spent else None
