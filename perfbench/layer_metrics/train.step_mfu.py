"""(6N + 12LSD) x tokens/s over peak, N without the embedding table; the
tokens and seconds are those of the window's plain steps, as in
``train.step_ms``."""


def read(ctx):
    if not ctx.get("tokens"):
        return None
    per_token = ctx["flops"].train_flops_per_token(
        ctx["model"], ctx["step_cfg"]["seq_len"])
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * per_token * ctx["tokens"] / (ctx["seconds"] * peak)
