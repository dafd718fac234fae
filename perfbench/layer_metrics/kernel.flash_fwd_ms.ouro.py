"""Milliseconds of device time a traced step of the Ouro cell spends in the
flash forward kernel at 16 heads of 128 over 4096 tokens (each layer application's forward and the
forward its checkpoint makes again in the backward pass: 64 calls a step), found by the name the
program gives it (``flash_fwd``: ops/flash_attention.py) on the
``tpu_custom_call``s of the compiled step (perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_fwd")
