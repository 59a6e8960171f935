"""Engine batching under open-loop traffic: milliseconds of input
preparation (the dispatch of the bucket's prep program, which embeds the
inputs and draws a forward-only bucket's masks) per executable call in the
window, from the program's span counters (see _spans.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    if not ctx.open_loop:
        return None
    s = metric_reader("_spans")
    spans = s.window(ctx)
    if spans is None or not s.count(spans, s.CALL):
        return None
    return 1e3 * s.seconds(spans, s.INPUTS) / s.count(spans, s.CALL)
