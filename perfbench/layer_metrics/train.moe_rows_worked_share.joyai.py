"""The sorted rows the expert layers' passes work on, over all T x k sorted
pairs of a layer, mean over the five routers' layers (the
multi-token-prediction module's among them): the program's own counter
(``rows_worked`` of parallel/moe.py, the function its passes take their trip
count from: whole row tiles over the pairs routed to the held experts) at the
tokens each held expert got (``moe_load``: the weights of the seed the window
ran and its first batch, one forward pass). 100 is a layer whose gathers,
row-wise passes and scatter-adds run over every sorted pair. In a JoyAI cell 16
of 256 experts are held, so even routing sends 2,048 of a layer's 32,768 pairs
here, exactly four row tiles of 512 (6.25): a layer with one pair more works a
fifth tile (7.8125). Nothing where the program has no such counter."""


def read(ctx):
    load = getattr(ctx.get("family"), "moe_load", None)
    try:
        from ray_tpu.parallel.moe import rows_worked
    except ImportError:
        return None
    sizes = load(ctx["model"], ctx["step_cfg"]) if load else None
    if not sizes:
        return None
    hp = ctx["step_cfg"]
    pairs = hp["batch"] * hp["seq_len"] * ctx["model"]["num_experts_per_tok"]
    worked = [int(rows_worked(held)) for _kind, per_layer in
              sorted(sizes.items()) for held in per_layer]
    return 100.0 * sum(worked) / (len(worked) * pairs)
