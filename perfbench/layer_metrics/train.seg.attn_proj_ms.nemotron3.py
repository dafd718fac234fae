"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.attn_proj``, forward and
backward: the attention layer's norm, q/k/v projections (no rotary
embedding), ``W_o`` and residual add (``_project_qkv``, ``_attn_out``);
models/transformer.py. The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.attn_proj") if spent else None
