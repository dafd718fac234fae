"""The held experts' grouped products' share of their roofline, against the
whole of ``seg.moe_experts``: the least time the chip could take for the
three grouped products of the expert layers of a step, forward and backward,
at the pairs even routing sends here (the family's ``experts_train_cost``:
the larger of operations over peak and bytes over bandwidth), over the device
time a traced step spends under ``seg.moe_experts``, which also holds the
gather of the pairs' rows, the weighted scatter-add and the residual. The
kernels alone are ``kernel.moe_gmm_roofline.lfm2``."""


def read(ctx):
    from perfbench import segments

    cost = getattr(ctx.get("family"), "experts_train_cost", None)
    spent = segments.segment_ms(ctx, "seg.moe_experts")
    if cost is None or not spent:
        return None
    hp = ctx["step_cfg"]
    least, _bound = ctx["flops"].roofline_seconds(
        cost(ctx["model"], hp["batch"], hp["seq_len"]), ctx["peak"])
    return 100.0 * least / (spent / 1e3)
