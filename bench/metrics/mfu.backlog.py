"""Whole explain step under closed-loop traffic: model FLOPs of the
explanations answered in the window over (window x the chip's bf16 peak),
in percent."""


def read(ctx):
    if ctx.open_loop:
        return None
    done = ctx.completed()
    if not done:
        return None
    flops = sum(ctx.explanation_flops(r) for r in done)
    return 100.0 * flops / (ctx.win.elapsed * ctx.peak["bf16_flops_per_s"])
