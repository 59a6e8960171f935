"""Device under open-loop traffic: percent of the time with at least one
request outstanding in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None or not ctx.open_loop:
        return None
    out = ctx.outstanding()
    total = sum(e - s for s, e in out)
    if not total:
        return None
    return 100.0 * (1.0 - ctx.busy_within(out) / total)
