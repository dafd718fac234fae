"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.kda_proj``, forward and backward: a
KDA layer's operator around its recurrence: the operator norm, the five
projections (q, k, v, the decay's, the output gate's) and beta's, the causal
taps and SiLU of q, k and v, their L2 norms, the decay and both gates, the
output norm, ``W_o`` and the residual add (``_kda_residual``);
models/transformer.py. The family's join places an instruction
(perfbench/families/ling3/join.py: perfbench/segments.py's join through the
compiled step, and a fusion no matmul decides to the class of two thirds of its
operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.kda_proj") if spent else None
