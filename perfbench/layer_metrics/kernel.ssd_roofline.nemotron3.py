"""The selective scan's share of its roofline in the traced steps: the least
time the chip could take for the chunk-128 algorithm's forward and backward
over the Mamba layers of a step (the family's ``ssd_train_cost``: the larger
of operations over peak and bytes over bandwidth) over the device time of
``seg.mamba_core``. Whatever implements the scan (``ssd_chunk`` of ops/ssd.py:
plain XLA operations, which no kernel's name marks) runs under that segment,
which is written around it and nothing else (models/transformer.py), so the
time is the segment's, by the family's join
(perfbench/families/nemotron_h/join.py). The backward pass makes the forward
again, which the cost does not count. Nothing where the step has no such
segment or the family no such cost."""

import sys


def read(ctx):
    family = ctx.get("family")
    cost = getattr(family, "ssd_train_cost", None)
    spent = getattr(family, "segment_ms", None)
    spent = spent(ctx, "seg.mamba_core") if spent else None
    if cost is None or not spent or spent <= 0:
        return None
    hp = ctx["step_cfg"]
    least, bound = ctx["flops"].roofline_seconds(
        cost(ctx["model"], hp["batch"], hp["seq_len"]), ctx["peak"])
    print(f"perfbench kernel.ssd_roofline.nemotron3: {spent:.6f} ms a step "
          f"under seg.mamba_core, least {1e3 * least:.6f} ms ({bound})",
          file=sys.stderr, flush=True)
    return 100.0 * least / (spent / 1e3)
