"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.embed``, forward and
backward: the table's cast and gather; backward the scatter-add
(``_embed``); models/transformer.py. The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.embed") if spent else None
