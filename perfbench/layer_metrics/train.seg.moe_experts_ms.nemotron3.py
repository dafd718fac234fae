"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.moe_experts``, forward and
backward: the held experts: the gather of the routed pairs' latent rows, two
grouped products with the squared ReLU's row pass between, the weighted
scatter-add (``parallel/moe.py:held_experts``), the experts' weights' casts;
models/transformer.py. The family's join places an instruction
(perfbench/families/nemotron_h/join.py: perfbench/segments.py's join through
the compiled step, and a fusion no matmul decides to the class of two thirds
of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.moe_experts") if spent else None
