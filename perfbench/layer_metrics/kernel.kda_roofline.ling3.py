"""The KDA recurrence's share of its roofline in the traced steps: the
least time the chip could take for the chunk-64 algorithm's forward and
backward over the KDA layers of a step (the family's ``kda_train_cost``: the
larger of operations over peak and bytes over bandwidth; at this cell's
shapes the bytes bound it) over the device time of ``seg.kda_core``.
Whatever implements the recurrence (``kda_chunk`` of ops/kda.py: plain XLA
operations, which no kernel's name marks) runs under that segment, which is
written around it and nothing else (models/transformer.py), so the time is
the segment's, by the family's join (perfbench/families/ling3/join.py). The
backward pass recomputes the forward, which the cost does not count. Nothing
where the step has no such segment or the family no such cost."""

import sys


def read(ctx):
    family = ctx.get("family")
    cost = getattr(family, "kda_train_cost", None)
    spent = getattr(family, "segment_ms", None)
    spent = spent(ctx, "seg.kda_core") if spent else None
    if cost is None or not spent or spent <= 0:
        return None
    hp = ctx["step_cfg"]
    least, bound = ctx["flops"].roofline_seconds(
        cost(ctx["model"], hp["batch"], hp["seq_len"]), ctx["peak"])
    print(f"perfbench kernel.kda_roofline.ling3: {spent:.6f} ms a step under "
          f"seg.kda_core, least {1e3 * least:.6f} ms ({bound})",
          file=sys.stderr, flush=True)
    return 100.0 * least / (spent / 1e3)
