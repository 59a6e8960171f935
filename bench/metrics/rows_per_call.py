"""Engine batching: real rows per plan-bucket executable call in the window
(start or fixed-budget calls; ladder hops are not plan buckets), from the
change in the engine's per-bucket counters."""


def read(ctx):
    b0, b1 = ctx.win.stats_before.buckets, ctx.win.stats_after.buckets
    calls = sum(v.calls - (b0[k].calls if k in b0 else 0) for k, v in b1.items())
    rows = sum(v.requests - (b0[k].requests if k in b0 else 0) for k, v in b1.items())
    return rows / calls if calls else None
