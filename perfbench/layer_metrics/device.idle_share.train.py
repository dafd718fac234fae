"""1 - busy / window of the traced slice."""


def read(ctx):
    t = ctx.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
