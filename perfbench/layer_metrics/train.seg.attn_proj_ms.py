"""Milliseconds of device self time a traced step spends in operations whose
outermost segment is ``seg.attn_proj``, forward and backward:
the attention norm, the q/k/v projections with rope and the output projection with its residual add (``_project_qkv``, ``_attn_out``); models/transformer.py. perfbench/segments.py joins
the trace's instruction names to the scopes through the compiled step."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, "seg.attn_proj")
