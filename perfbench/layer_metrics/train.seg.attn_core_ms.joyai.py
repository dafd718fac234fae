"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.attn_core``, forward and backward:
the five stack layers' attention: the shared rotary key part broadcast to the
32 heads, the transposes and the three flash kernels at 192 / 128
(``_mla_keys``, ``_attention_dense``); models/transformer.py. The module's
layer is ``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.attn_core") if spent else None
