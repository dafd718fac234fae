"""Milliseconds of device self time a traced step of a JoyAI cell spends in
operations whose outermost segment is ``seg.attn_proj``, forward and backward:
the five stack layers' latent-attention projections: the operator norm, the
query latent (``W_qa``, its norm, ``W_qb``), ``W_kva``, the latent's norm,
``W_kvb``, rope on the rotary parts, ``W_o`` and the residual add
(``_project_mla``, ``_mla_out``); models/transformer.py. The module's layer is
``seg.mtp``'s.
The family's join places an instruction
(perfbench/families/joyai/join.py: perfbench/segments.py's join through the
compiled step, and the Ling family's rule: a fusion no matmul decides goes to
the class of two thirds of its operations)."""


def read(ctx):
    spent = getattr(ctx.get("family"), "segment_ms", None)
    return spent(ctx, "seg.attn_proj") if spent else None
