"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.moe_latent``, forward and
backward: the expert layers' two projections round the latent width the
routed experts work at: every token down to it before the row passes, the
experts' weighted sum up from it after (``_moe_residual``);
models/transformer.py. The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_latent") if spent else None
