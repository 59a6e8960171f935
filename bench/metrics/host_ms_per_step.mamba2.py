"""Scheduler host path in the Mamba-2 cell: host milliseconds per scheduler
step in the window (see _spans.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    spans = metric_reader("_spans")
    if ctx.open_loop or not spans.is_mamba2(ctx):
        return None
    return spans.host_ms_per_step(ctx)
