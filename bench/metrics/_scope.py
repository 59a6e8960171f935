"""Shared by the readers of device time under the program's scopes: the
``jax.named_scope`` names (``repro.stage2``, ``repro.probe``,
``repro.perturb``) that each operation carries in the trace (``trace.py``
keeps them; ``ctx.trace["scopes_s"]`` holds the window's device seconds by
outermost scope). A run without a trace, or a scope with no device time in
the window, reads as None."""
from bench.harness import trace as tr


def seconds(ctx, scope):
    """Device seconds of the window's leaf operations under ``scope``, at any
    depth (averaged over devices), or None."""
    if ctx.trace is None:
        return None
    return tr.scope_seconds(ctx.events, ctx.trace["window_ns"], scope) or None


def calls(ctx, is_program):
    """Calls, started in the window, of the programs whose name passes
    ``is_program`` (averaged over devices), from the trace's module events."""
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace["window_ns"]
    mods = ctx.events["modules"]
    n = sum(1 for evs in mods.values() for name, s, _ in evs
            if lo <= s < hi and is_program(tr.program(name)))
    return n / len(mods) if mods else None


def roofline(ctx, scope, flops, bytes_):
    """Percent of its roofline the work under ``scope`` ran at: the least
    time of ``flops`` operations and ``bytes_`` bytes at the chip's peaks
    (``bench/peaks.json``: bf16 FLOP/s, HBM bytes/s), over the scope's
    device seconds. The operations and bytes come from functions kept with
    the configuration (``bench/configs/<config>.py``)."""
    s = seconds(ctx, scope)
    if s is None or not (flops or bytes_):
        return None
    least = max(flops / ctx.peak["bf16_flops_per_s"], bytes_ / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / s
