"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.embed``, forward and backward:
the embedding of the step's tokens: the 16,160-row table's cast and gather,
backward the scatter-add (``_embed``); models/transformer.py. The module's
second look-up (each position's next token) is ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.embed") if spent else None
