"""The flash kernels' share of their roofline in the traced steps: the
least time the chip could take for the forward, dq and dk/dv kernels of
those steps (perfbench/flops.py, causal work, the larger of FLOPs/peak
and bytes/bandwidth; at these shapes compute bounds it) over the device
time of those kernels in the trace. No ``jax.named_scope`` names them
yet: they are the step's only Pallas kernels, so they are found as the
operations that call ``tpu_custom_call``. Nothing found: nothing returned."""

NEEDLES = ('custom_call_target="tpu_custom_call"',)


def read(ctx):
    from perfbench import trace_reduce

    planes, steps = ctx.get("planes"), ctx.get("traced_steps")
    if not planes or not steps:
        return None
    spent = trace_reduce.kernel_seconds(
        trace_reduce.device_ops(planes), NEEDLES)
    if spent <= 0:
        return None
    cost = ctx["flops"].flash_train_cost(
        ctx["model"], ctx["step_cfg"]["batch"], ctx["step_cfg"]["seq_len"])
    least, _bound = ctx["flops"].roofline_seconds(cost, ctx["peak"])
    return 100.0 * least * steps / spent
