"""The fullest held expert's tokens over the mean of the held experts', the
largest over the five routers (the four expert layers' and the
multi-token-prediction module's, under ``mtp``): the program's own counter
(``moe_load`` of models/transformer.py, from the layers' routing code) at the
weights of the seed the window ran and its first batch, one forward pass. 1 is
even routing among the 16 held; the sum a router (printed beside it) against
the 2,048 pairs even routing over all 256 experts sends here says how many row
tiles the layer worked. Nothing where the program has no such counter."""

import sys


def read(ctx):
    load = getattr(ctx.get("family"), "moe_load", None)
    sizes = load(ctx["model"], ctx["step_cfg"]) if load else None
    if not sizes:
        return None
    worst = 0.0
    for kind, per_layer in sorted(sizes.items()):
        for layer, held in enumerate(per_layer):
            print(f"perfbench moe_load {kind}.{layer}: "
                  f"{[int(n) for n in held]} sum {int(held.sum())}",
                  file=sys.stderr, flush=True)
            if held.sum() > 0:
                worst = max(worst, float(held.max() / held.mean()))
    return worst or None
