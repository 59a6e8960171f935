"""Scheduler host path under open-loop traffic: host milliseconds per
scheduler step in the window, from the program's span counters (see
_spans.py): step time less device waits and compiles, over the steps."""
from bench.harness.spec import metric_reader


def read(ctx):
    if not ctx.open_loop:
        return None
    return metric_reader("_spans").host_ms_per_step(ctx)
