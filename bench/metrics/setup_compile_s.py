"""Executable cache: seconds set-up spent on executable-cache misses
(compiles, loads from the persistent compilation cache, and attempts the
compiler refused), from the program's span counters as the window opens
(see _spans.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    spans = getattr(ctx.win.stats_before, "spans", None)
    if spans is None:
        return None
    s = metric_reader("_spans")
    return s.seconds(spans, s.COMPILE)
