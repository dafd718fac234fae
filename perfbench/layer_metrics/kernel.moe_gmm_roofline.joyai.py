"""The grouped-matmul kernels' share of their roofline in the traced steps:
the least time the chip could take for the held experts' three grouped
products of a step, forward and backward, at the pairs even routing sends
here (the family's ``experts_train_cost``: 2,048 pairs a layer in five expert
layers, the multi-token-prediction module's among them), over the device time
of the kernels that compute them, found by the names the program gives their
calls (``moe_gmm``: the products and the gradients of their left operands;
``moe_tgmm``: the gradients of the weights; ops/grouped_matmul.py). Either
missing: the cost is not this step's, and nothing is returned. Twelve products
run, the forward's three twice, for the nine the cost counts: the ceiling is 75
%. A batch that routes more pairs here than even routing would reads lower:
``train.moe_load_max_over_mean.joyai`` stands beside it."""

import sys

KERNELS = ("moe_gmm", "moe_tgmm")


def read(ctx):
    from perfbench import segments

    cost = getattr(ctx.get("family"), "experts_train_cost", None)
    spent = [segments.kernel_ms(ctx, k) for k in KERNELS]
    if cost is None or None in spent or sum(spent) <= 0:
        return None
    print(f"perfbench kernel.moe_gmm_roofline.joyai: moe_gmm {spent[0]:.6f} "
          f"ms a step, moe_tgmm {spent[1]:.6f}", file=sys.stderr, flush=True)
    hp = ctx["step_cfg"]
    least, _bound = ctx["flops"].roofline_seconds(
        cost(ctx["model"], hp["batch"], hp["seq_len"]), ctx["peak"])
    return 100.0 * least / (sum(spent) / 1e3)
