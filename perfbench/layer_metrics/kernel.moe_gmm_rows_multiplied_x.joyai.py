"""The rows the grouped expert kernels multiply over the pairs routed to the
held experts, summed over the five routers' layers (the multi-token-prediction
module's among them): the program's own counter (``rows_multiplied`` of
ops/grouped_matmul.py, from the rule its kernels take their visits from: one
visit a row tile a group touches, every row of a visit multiplied) at the
tokens each held expert got (``moe_load``: the weights of the seed the window
ran and its first batch, one forward pass). 1 is a kernel that multiplies a
routed row once and no other; every group that begins inside a row tile costs
up to a tile more, so 16 groups of about 128 rows read near 2 at the kernels'
128-row tiles. Nothing where the program has no such counter."""


def read(ctx):
    load = getattr(ctx.get("family"), "moe_load", None)
    try:
        from ray_tpu.ops.grouped_matmul import rows_multiplied
    except ImportError:
        return None
    sizes = load(ctx["model"], ctx["step_cfg"]) if load else None
    if not sizes:
        return None
    hp = ctx["step_cfg"]
    pairs = hp["batch"] * hp["seq_len"] * ctx["model"]["num_experts_per_tok"]
    layers = [held for _kind, per_layer in sorted(sizes.items())
              for held in per_layer]
    routed = sum(int(held.sum()) for held in layers)
    if not routed:
        return None
    return sum(int(rows_multiplied(held, pairs)) for held in layers) / routed
