"""Attribution core under backlog traffic: device milliseconds under the
program's ``repro.probe`` scope (the stage-1 probe of the paper schedule)
per explanation answered in the window (see _scope.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if ctx.open_loop:
        return None
    s = metric_reader("_scope").seconds(ctx, "repro.probe")
    done = len(ctx.completed())
    return 1e3 * s / done if s is not None and done else None
