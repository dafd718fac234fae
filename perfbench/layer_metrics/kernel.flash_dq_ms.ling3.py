"""Milliseconds of device time a traced step of a Ling cell spends in the
flash backward kernel that makes dq, at queries and keys 192 wide and
values 128 wide (the one MLA layer's 8 heads over 4096 tokens), found by the
name the program gives it (``flash_bwd_dq``: ops/flash_attention.py) on the
``tpu_custom_call``s of the compiled step (perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_bwd_dq")
