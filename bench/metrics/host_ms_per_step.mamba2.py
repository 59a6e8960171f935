"""Scheduler host path in the Mamba-2 cell: host milliseconds per scheduler
step in the window (see _spans.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if ctx.open_loop:
        return None
    return metric_reader("_spans").host_ms_per_step(ctx)
