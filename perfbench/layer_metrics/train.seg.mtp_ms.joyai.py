"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.mtp``, forward and backward:
the multi-token-prediction module whole, which is outermost on its path: its
two input norms, the second look-up of the table, the joint projection, its
layer (latent attention at 32 heads, router, shared expert, the 16 experts
held), its output norm, its pass through the model's head and its loss
(``_mtp_loss``); models/transformer.py. What the module's layer spends is
counted here and in none of the main stack's segments; the kernels' readers,
which go by name, count its kernels with the stack's.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.mtp") if spent else None
