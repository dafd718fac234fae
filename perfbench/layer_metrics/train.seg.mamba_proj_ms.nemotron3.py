"""Milliseconds of device self time a traced step of a Nemotron-H cell spends
in operations whose outermost segment is ``seg.mamba_proj``, forward and
backward: the Mamba-2 layers round their scan: the pre-norm, ``W_in``, the
causal taps with their bias and the SiLU, the step's softplus, the gated
norm over a group's channels, ``W_out`` and the residual add
(``_mamba_inputs``, ``_mamba_out``); the backward pass keeps the products'
outputs and makes the elementwise chains again; models/transformer.py. The
family's join places an instruction (perfbench/families/nemotron_h/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no
matmul decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.mamba_proj") if spent else None
