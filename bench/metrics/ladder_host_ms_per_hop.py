"""Adaptive ladder: host milliseconds per ladder hop in the window, from the
program's span counters (see _spans.py): schedule refinement, the survivors'
gather and re-pad, and the read-backs and survivor selection, over the
change in the engine's hop calls."""
from bench.harness.spec import metric_reader


def read(ctx):
    s = metric_reader("_spans")
    spans = s.window(ctx)
    if spans is None:
        return None
    hops = ctx.win.stats_after.adaptive.hop_calls - ctx.win.stats_before.adaptive.hop_calls
    if not hops:
        return None
    return 1e3 * s.seconds(spans, *s.LADDER) / hops
