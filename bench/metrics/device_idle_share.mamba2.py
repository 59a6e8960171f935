"""Device in the Mamba-2 cell: percent of the traced window in which no
operation ran on the device (see device_idle_share.backlog.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    return metric_reader("device_idle_share.backlog").read(ctx)
