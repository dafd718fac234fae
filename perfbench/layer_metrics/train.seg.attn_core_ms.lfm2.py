"""Milliseconds of device self time a traced step of an LFM2 cell spends in
operations whose outermost segment is ``seg.attn_core``, forward and backward:
what stands between q, k, v and o in the attention layer: K/V repeated to the query-head width, the transposes and the three flash kernels at heads of 64 (``_attention_dense``); models/transformer.py. perfbench/segments.py joins the trace's
instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.attn_core")
