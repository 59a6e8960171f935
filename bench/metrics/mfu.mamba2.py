"""Whole explain step in the Mamba-2 cell: model FLOPs of the explanations
answered in the window over (window x the chip's bf16 peak), in percent
(see mfu.backlog.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    return metric_reader("mfu.backlog").read(ctx)
