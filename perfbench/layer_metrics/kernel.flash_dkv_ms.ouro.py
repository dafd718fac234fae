"""Milliseconds of device time a traced step of the Ouro cell spends in the
flash backward dk/dv kernel at 16 heads of 128 over 4096 tokens (32 calls a step), found by the name the
program gives it (``flash_bwd_dkv``: ops/flash_attention.py) on the
``tpu_custom_call``s of the compiled step (perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_bwd_dkv")
