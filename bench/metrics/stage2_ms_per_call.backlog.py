"""Attribution core and model stage 2 under backlog traffic: device
milliseconds under the program's ``repro.stage2`` scope per call of a
gradient program (start, hop, fixed-budget or remat, as _grad_roofline.py
names them) in the window, from the trace (see _scope.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if ctx.open_loop:
        return None
    scope = metric_reader("_scope")
    s = scope.seconds(ctx, "repro.stage2")
    n = scope.calls(ctx, metric_reader("_grad_roofline").is_grad_program)
    return 1e3 * s / n if s is not None and n else None
