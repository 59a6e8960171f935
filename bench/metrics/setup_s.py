"""Set-up seconds: from the process's start to the window's (JAX start-up,
weights, engine, inputs and the warm-up that builds every executable)."""


def read(ctx):
    return ctx.setup_s
