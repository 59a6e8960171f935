"""Reduce a profiler trace to busy time, kernel times and named idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each ``[name, start_ns, duration_ns]`` on one clock:

    ops      per device: every operation the device ran ("XLA Ops" line)
    modules  per device: every program (XLA module) call ("XLA Modules")
    host     the harness's own spans ("bench.*" annotations)

``reduce`` turns them into what the metrics read: the device's busy time
(the union of its operations' intervals) inside the window, each
program's device time, the operations that took most time, and the idle
gaps, each named by the innermost harness span open at its midpoint.

Run as a script on a trace directory, it prints what the trace holds:

    python3 bench/harness/trace.py <trace-dir>
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def start(trace_dir: str) -> None:
    """Trace the device and the harness's spans; not every Python call
    (the Python tracer would slow the host path it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if is_device_plane(plane.name):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def program(module: str) -> str:
    """An XLA module's name without its fingerprint: ``jit_hop_fn(1234)``
    -> ``jit_hop_fn`` (each compiled shape is a module of its own)."""
    return module.split("(", 1)[0]


def op_name(op: str) -> str:
    """An operation's HLO name without its text: ``%fusion.12 = f32[...]
    fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def leaves(events: list) -> list:
    """The operations that contain no other: a loop's event spans its body's
    events, which are listed too."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def merge(intervals: list) -> list:
    """Union of [start, end] intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def window_bounds(ev: dict) -> tuple[float, float]:
    spans = [h for h in ev["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, s, d = spans[-1]
    return s, s + d


def name_at(host: list, starts: list, t: float) -> str:
    """The harness span open at time t. ``host`` holds the harness's spans
    but the window's, sorted by start; they run one after another on one
    thread, so the last one to start before t is the only candidate."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < host[i][1] + host[i][2]:
        return host[i][0]
    return "no span"


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and idle seconds (averaged over devices), per-program device
    seconds, the longest-running operations and the idle gaps by name,
    all inside the window span; times in seconds."""
    lo, hi = window_bounds(ev)
    devices = sorted(ev["ops"])
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = {d: clip(merge([[s, s + w] for _, s, w in ev["ops"][d]]), lo, hi) for d in devices}
    ops: dict = defaultdict(float)
    for d in devices:
        mods = sorted(ev["modules"].get(d, []), key=lambda e: e[1])
        mod_starts = [m[1] for m in mods]
        for name, s, w in leaves(ev["ops"][d]):
            if lo <= s < hi:
                i = bisect.bisect_right(mod_starts, s) - 1
                mod = program(mods[i][0]) if i >= 0 and s < mods[i][1] + mods[i][2] else "?"
                ops[f"{mod}/{op_name(name)}"] += w
    modules: dict = defaultdict(list)
    for d in ev["modules"]:
        for name, s, w in ev["modules"][d]:
            modules[program(name)].append([s, s + w])
    host = sorted((h for h in ev["host"] if h[0] != WINDOW_SPAN), key=lambda h: h[1])
    starts = [h[1] for h in host]
    gaps: dict = defaultdict(float)
    for d in devices:
        edges = [lo] + [x for iv in busy[d] for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps[name_at(host, starts, 0.5 * (s + e))] += (e - s) / len(devices)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "window_ns": [lo, hi],
        "busy_s": sum(length(b) for b in busy.values()) / len(devices) * ns,
        "busy_ns": busy,
        "modules_s": {k: length(clip(merge(v), lo, hi)) * ns for k, v in modules.items()
                      if clip(v, lo, hi)},
        "device_ops": sorted(([k, v * ns / len(devices)] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * ns] for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
    }


def main(argv: list) -> int:
    path = find_xplane(argv[0])
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = defaultdict(int)
            for e in evs:
                names[e.name] += 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events; first "
                  f"{[(e.name, e.start_ns, e.duration_ns) for e in evs[:2]]}; most {common}")
    ev = load(path)
    print(json.dumps({k: (len(v) if isinstance(v, list) else {d: len(x) for d, x in v.items()})
                      for k, v in ev.items()}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
