"""Attribution core and model stage 2 under backlog traffic: the gradient
programs' share of their roofline, in percent (see _grad_roofline.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if ctx.open_loop != ("backlog" == "poisson"):
        return None
    return metric_reader("_grad_roofline").read(ctx)
