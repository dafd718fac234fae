"""Milliseconds of device time a traced step of a JoyAI cell spends in the
flash forward, at queries and keys 192 wide and values 128 wide
(six layer bodies, the multi-token-prediction module's among them, 32 heads
over 4096 tokens each), found by the name the program gives it (``flash_fwd``:
ops/flash_attention.py) on the ``tpu_custom_call``s of the compiled step
(perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_fwd")
