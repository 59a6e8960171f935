"""One run of one cell, from set-up to the result line.

``run_cell`` sets the cell up, measures it for the window (traced with
``trace=True``), reads its metrics, frees the program, holds a sample of
the window's answers to the plain reference, and returns the result: the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
optionally ``breakdown``, and last ``checks`` (each number compared, with
its limit).

The limits of a cell are in ``bench/limits/<workload>.json``:
``{"sample": <answers compared>, "limits": {<number>: <limit>}}``, the
numbers being those of ``reference.compare``, each read as its worst over
the answers compared.
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import time
from typing import Optional

import numpy as np

from bench.harness import spec

LIMITS = spec.BENCH / "limits"
TRACE_DIR = spec.ROOT / ".bench_trace"
UNFINISHED_MS = 1e12  # an infinite latency, as JSON can carry it


def limits_for(workload: str) -> dict:
    with open(LIMITS / f"{workload}.json") as fh:
        return json.load(fh)


def sample(results: list, n: int, seed: int) -> list:
    """The answers to compare: the longest (most tokens, then most steps)
    and the rest drawn from the seed."""
    if not results:
        return []
    size = lambda x: (len(x[1]["tokens"]), x[2].get("m_used", 0))
    longest = max(range(len(results)), key=lambda i: size(results[i]))
    rest = [i for i in range(len(results)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(1,)))
    picked = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [results[longest]] + [results[rest[int(i)]] for i in sorted(picked)]


def seq_bucket(sizes: dict, n: int) -> int:
    return min(b for b in sizes["engine"]["seq_buckets"] if b >= n)


def check(info: dict, params, chosen: list, limits: dict, log=print) -> dict:
    """Hold each chosen answer to the plain reference; the worst reading of
    each number over the answers, beside its limit."""
    from bench.harness import reference as ref

    rows: dict = {}
    model, sizes = info["model"], info["sizes"]
    for rec, inp, got in chosen:
        row = ref.Plain(model, sizes, params, inp, "f32")
        nums = ref.compare(row, info["traffic"], seq_bucket(sizes, row.n_real), got,
                           limits.get("endpoint_err", 0.0))
        log(f"check request {rec.index}: " + " ".join(f"{k}={v:.6g}" for k, v in nums.items()))
        for k, v in nums.items():
            rows.setdefault(k, []).append(v)
    return {k: {"value": max(rows[k]) if k in rows else math.inf, "limit": lim}
            for k, lim in limits.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, allow_cpu: bool = False,
             sizes: Optional[dict] = None, traffic: Optional[dict] = None,
             limits: Optional[dict] = None, log=print) -> dict:
    """One run; returns the result dict. ``allow_cpu`` (rehearsals and
    tests only) runs on whatever JAX finds and reports no metric: a number
    from another device is never written under a device metric's name."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    from bench.harness import loop, measure, trace as tr

    bm = spec.load_benchmark()
    info = spec.cell(bm, workload)
    if sizes is not None:
        info["sizes"] = sizes
    if traffic is not None:
        info["traffic"] = traffic
    limits = limits if limits is not None else limits_for(workload)
    dev = jax.devices()
    on_chip = dev[0].platform == "tpu"
    if not on_chip and not allow_cpu:
        raise RuntimeError(f"no TPU: JAX reports platform {dev[0].platform!r}")
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev)}
    log(f"device: {device}")

    run = loop.CellRun(info, seed, seconds)
    run.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} inputs={len(run.inputs)} "
        f"compiles={run.engine.stats.misses}")
    trace_dir = TRACE_DIR / f"{workload}-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr.start(str(trace_dir))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        win = run.window()
    # wait for the requests still owed before anything else: stopping the
    # trace takes seconds that must not eat into the wait
    live = len(run.live)
    t_drain = time.perf_counter()
    run.drain(win)
    log(f"drain: live_at_close={live} left={len(run.live)} "
        f"drain_s={time.perf_counter() - t_drain:.3f}")
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace: stop_s={time.perf_counter() - t_stop:.3f}")
    device["memory_peak_bytes"] = loop.memory_peak_bytes()
    log(f"window: elapsed_s={win.elapsed:.3f} steps={win.steps} requests={len(win.records)} "
        f"compiles_in_window misses={win.misses} backend={win.backend_compiles}")
    log(f"host: step_max_s={win.step_max_s:.4f} gc_collections={len(win.gc_pauses)} "
        f"gc_pause_max_s={max(win.gc_pauses, default=0.0):.4f} "
        f"gc_pause_total_s={sum(win.gc_pauses):.4f}")
    results = run.results(win)
    if run.due is not None:  # every request due in the window is owed an answer
        failed = sum(not r.ok for r in win.records)
    else:  # closed loop: those still in flight at the close are not failures
        failed = sum(r.ticket.status in loop.DONE and not r.ok for r in win.records)
    for r in win.records:
        if not r.ok and (run.due is not None or r.ticket.status in loop.DONE):
            log(f"failed request {r.index}: status={r.ticket.status} due_s={r.due:.3f} "
                f"submit_s={r.submit:.3f} finish_s={r.finish:.3f}")
    if run.due is not None and win.records:
        lat = [r.latency for r in win.records]
        log(f"latency: p50_ms={1e3 * measure.nearest_rank(lat, 0.5):.3f} "
            f"p95_ms={1e3 * measure.nearest_rank(lat, 0.95):.3f} "
            f"max_ms={1e3 * max(lat):.3f}")
    chosen = sample(results, limits["sample"], seed)
    params = run.params
    run.free()

    ctx = measure.Ctx(info["sizes"], info["model"], info["traffic"],
                      measure.peaks_for(device["kind"]) if on_chip else {}, win, setup_s)
    out: dict = {"correct": False, "attempted": len(win.records), "failed": failed,
                 "metrics": {}, "device": device}
    if trace and on_chip:
        ctx.events = tr.load(tr.find_xplane(str(trace_dir)))
        ctx.trace = tr.reduce(ctx.events)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                            "idle_gaps": ctx.trace["idle_gaps"]}
    if on_chip:
        for m in spec.cell_metrics(bm, workload, trace):
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                v = UNFINISHED_MS if math.isinf(v) else float(v)
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    checks = check(info, params, chosen, limits["limits"], log)
    out["correct"] = bool(chosen) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']:.6g} limit {c['limit']:.6g}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out, allow_nan=False, default=_num), flush=True)


def _num(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    raise TypeError(type(x))
