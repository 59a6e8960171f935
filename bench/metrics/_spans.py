"""Shared by the readers of the program's span counters: ``EngineStats.spans``
maps a span name to ``(count, seconds)``, each span's wall time including
the spans nested inside it (``repro.serve.spans`` names them). A program
without the counters reads as None, and its readers then report nothing."""

STEP = "repro.sched.step"
COMPILE = "repro.engine.compile"
CALL = "repro.engine.call"
WAIT = "repro.engine.wait"
INPUTS = "repro.engine.inputs"
MASKS = "repro.engine.masks"
LADDER = ("repro.ladder.refine", "repro.ladder.gather", "repro.ladder.readback")


def window(ctx):
    """{span: (count, seconds)} added inside the window, or None."""
    s0 = getattr(ctx.win.stats_before, "spans", None)
    s1 = getattr(ctx.win.stats_after, "spans", None)
    if s0 is None or s1 is None:
        return None
    return {k: (n - s0.get(k, (0, 0.0))[0], s - s0.get(k, (0, 0.0))[1])
            for k, (n, s) in s1.items()}


def seconds(spans, *names):
    return sum(spans.get(k, (0, 0.0))[1] for k in names)


def count(spans, name):
    return spans.get(name, (0, 0.0))[0]


def is_mamba2(ctx):
    return ctx.sizes.get("name") == "mamba2-780m"


def host_ms_per_step(ctx):
    """Host milliseconds per scheduler step in the window: the step spans'
    time less the device waits and compiles inside them, over their count."""
    spans = window(ctx)
    if spans is None or not count(spans, STEP):
        return None
    host = seconds(spans, STEP) - seconds(spans, WAIT, COMPILE)
    return 1e3 * host / count(spans, STEP)
