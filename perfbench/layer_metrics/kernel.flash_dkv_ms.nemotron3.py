"""Milliseconds of device time a traced step of a Nemotron-H cell spends in the
flash backward's dk/dv kernel, at heads of 128 (the one attention layer's 16
heads over 4096 tokens), found by the name the program gives it
(``flash_bwd_dkv``: ops/flash_attention.py) on the ``tpu_custom_call``s of
the compiled step (perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_bwd_dkv")
