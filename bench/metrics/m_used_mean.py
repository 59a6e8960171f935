"""Adaptive ladder: gradient steps per request in the window (the change in
the engine's total steps over the change in requests served adaptively)."""


def read(ctx):
    a0, a1 = ctx.win.stats_before.adaptive, ctx.win.stats_after.adaptive
    n = a1.requests - a0.requests
    return (a1.total_steps - a0.total_steps) / n if n else None
