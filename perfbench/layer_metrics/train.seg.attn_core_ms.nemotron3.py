"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.attn_core``, forward and
backward: the attention layer's K/V repeat, transposes and the three flash
kernels at 16 heads of 128 (``_attention_dense``); models/transformer.py.
The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.attn_core") if spent else None
