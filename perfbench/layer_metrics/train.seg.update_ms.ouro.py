"""Milliseconds of device self time a traced step of the Ouro cell spends in
operations that carry names and no model segment and lie outside the
differentiated function: the AdamW pass over 612 M parameters,
``apply_updates``, the batch draw (perfbench/segments.py). An update that XLA
fused into a segment's matmul is that segment's."""


def read(ctx):
    from perfbench import segments

    return segments.segment_ms(ctx, segments.UPDATE)
