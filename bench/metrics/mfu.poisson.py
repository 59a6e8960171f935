"""Whole explain step under open-loop traffic: model FLOPs of the
explanations answered in the window over (time with at least one request
outstanding x the chip's bf16 peak), in percent."""


def read(ctx):
    if not ctx.open_loop:
        return None
    done = ctx.completed()
    busy = sum(e - s for s, e in ctx.outstanding())
    if not done or not busy:
        return None
    flops = sum(ctx.explanation_flops(r) for r in done)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
