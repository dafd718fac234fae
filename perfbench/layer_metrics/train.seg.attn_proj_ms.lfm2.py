"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.attn_proj``, forward and backward:
the attention layer's norm, q/k/v projections, the RMSNorm of each head of q and k, rope, the output projection and its residual add (``_project_qkv``, ``_attn_out``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.attn_proj")
