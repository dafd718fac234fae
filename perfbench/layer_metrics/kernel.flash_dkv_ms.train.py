"""Milliseconds of device time a traced step spends in the flash backward dk/dv
kernel, found by the name the program gives it (``flash_bwd_dkv``:
``name=`` and the scope around the call in ops/flash_attention.py) on the
``tpu_custom_call``s of the compiled step (perfbench/segments.py)."""


def read(ctx):
    from perfbench import segments

    return segments.kernel_ms(ctx, "flash_bwd_dkv")
