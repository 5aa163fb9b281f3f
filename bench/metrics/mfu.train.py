"""Model FLOP utilization of the softsync round: model FLOPs per token
(``counts.train_flops_per_token``) times the window's tokens per second,
over the chips' bf16 peak, in percent.  Recomputed work is not counted."""


def read(ctx):
    if ctx.get("flops_per_token") is None:
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * ctx["flops_per_token"] * rate / peak
