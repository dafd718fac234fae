"""Milliseconds of device self time a traced step of a Ling cell spends in
operations whose outermost segment is ``seg.attn_proj``, forward and backward:
the MLA layer's projections: the operator norm, ``W_q``, ``W_kva``, the
latent's norm, ``W_kvb``, rope on the rotary parts, the heads' gate, ``W_o``
and the residual add (``_project_mla``, ``_mla_out``); models/transformer.py.
The family's join places an instruction (perfbench/families/ling3/join.py:
perfbench/segments.py's join through the compiled step, and a fusion no matmul
decides to the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.attn_proj") if spent else None
