"""Explanations answered inside the window per second of the window."""


def read(ctx):
    if ctx.open_loop:
        return None
    return len(ctx.completed()) / ctx.win.elapsed
