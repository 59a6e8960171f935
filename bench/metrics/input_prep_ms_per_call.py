"""Engine batching under open-loop traffic: milliseconds of input
preparation (embedding, host to device, and a forward-only bucket's mask
draw) per executable call in the window, from the program's span counters
(see _spans.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if not ctx.open_loop:
        return None
    s = metric_reader("_spans")
    spans = s.window(ctx)
    if spans is None or not s.count(spans, s.CALL):
        return None
    return 1e3 * s.seconds(spans, s.INPUTS, s.MASKS) / s.count(spans, s.CALL)
