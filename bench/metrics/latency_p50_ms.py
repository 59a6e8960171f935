"""Median milliseconds from a request's due time to its attribution, over
every request due in the window; one that fails, is refused, is degraded
or never finishes counts as infinitely late."""
from bench.harness.measure import nearest_rank


def read(ctx):
    if not ctx.open_loop or not ctx.win.records:
        return None
    return 1e3 * nearest_rank(ctx.latencies_s(), 0.50)
