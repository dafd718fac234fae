"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations that carry names and no model segment and lie outside the
differentiated function: the AdamW pass over 680 M parameters,
``apply_updates``, the batch draw. An update that XLA fused into a segment's
matmul is that segment's; one fused with the ``pad`` and ``add_any`` that finish
its gradient is counted here, by the family's join
(perfbench/families/joyai/join.py: the Ling family's rule, a fusion no matmul
decides goes to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "update") if spent else None
