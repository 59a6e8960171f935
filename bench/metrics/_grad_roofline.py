"""Shared by the grad_exec_roofline readers: the gradient programs' least
time for their launched work (the larger of FLOPs over the bf16 peak and
weight bytes over HBM bandwidth) over their device time in the trace, in
percent. Gradient programs are the engine's start, hop and fixed-budget
stage-2 executables (``remat_fn`` when the engine recompiled one with
per-layer recomputation)."""

GRAD_PROGRAMS = ("start_fn", "hop_fn", "attr_fn", "remat_fn")


def is_grad_program(name):
    return any(p in name for p in GRAD_PROGRAMS)


def read(ctx):
    if ctx.trace is None:
        return None
    work = ctx.launched_grad_work()
    if work is None:
        return None
    flops, calls = work
    device_s = sum(s for k, s in ctx.trace["modules_s"].items() if is_grad_program(k))
    if not device_s or not flops:
        return None
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                calls * ctx.model.param_bytes(ctx.sizes) / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / device_s
