"""The last pass's share of the exit distribution, in percent: ``p_T`` of the
program's own ``exit_weights`` (models/transformer.py: the gates' distribution
over the four passes, averaged over the positions) at the weights of the seed
the window ran and its first batch, one forward pass. What share of the loss
the deepest pass carries; the whole distribution is printed beside it. Nothing
where the program has no such function."""

import sys


def read(ctx):
    weights = getattr(ctx.get("family"), "exit_weights", None)
    shares = weights(ctx["model"], ctx["step_cfg"]) if weights else None
    if shares is None:
        return None
    print(f"perfbench exit_weights: {[float(p) for p in shares]}",
          file=sys.stderr, flush=True)
    return 100.0 * float(shares[-1])
