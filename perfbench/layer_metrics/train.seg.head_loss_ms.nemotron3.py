"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.head_loss``, forward and
backward: the final norm, the untied head over the 16,384-row slice, float32
logits, log-softmax, pick, mean; models/transformer.py. The family's join
places an instruction (perfbench/families/nemotron_h/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no
matmul decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.head_loss") if spent else None
