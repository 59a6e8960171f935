"""Explanations answered inside the window per second of the window, in
the Mamba-2 cell (its own metric, so that its own, narrower spread sets its
bound; see explain_per_s.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    return metric_reader("explain_per_s").read(ctx)
