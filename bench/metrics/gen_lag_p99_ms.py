"""Load generator: 99th-percentile milliseconds by which a request was
submitted after it fell due (the generator waits on the scheduler's steps;
a starved generator must not read as a fast server)."""
from bench.harness.measure import nearest_rank


def read(ctx):
    if not ctx.open_loop or not ctx.win.records:
        return None
    return 1e3 * nearest_rank([r.submit - r.due for r in ctx.win.records], 0.99)
