"""Attribution core and model stage 2 in the Mamba-2 cell: the gradient
programs' share of their roofline, in percent (see _grad_roofline.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if ctx.open_loop:
        return None
    return metric_reader("_grad_roofline").read(ctx)
