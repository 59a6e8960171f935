"""Reduce a profiler trace to busy time, kernel times, device time by the
program's scopes and named idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each ``[name, start_ns, duration_ns]`` on one clock:

    ops      per device: every operation the device ran ("XLA Ops" line),
             with a fourth entry, its scope: the ``repro.*`` names of the
             ``jax.named_scope``s it ran under, outermost first, joined by
             "/" ("" for none), from the ``tf_op`` stat of its event
             metadata
    modules  per device: every program (XLA module) call ("XLA Modules")
    host     the harness's spans ("bench.*") and the program's ("repro.*")

``reduce`` turns them into what the metrics read: the device's busy time
(the union of its operations' intervals) inside the window, each
program's device time, the operations that took most time, device time by
outermost scope, and the idle gaps, each named by the innermost span open
at its midpoint.

Run as a script on a trace directory, it prints what the trace holds:

    python3 bench/harness/trace.py <trace-dir>
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "repro.")
WINDOW_SPAN = "bench.window"
SCOPE_STAT = "tf_op"
# a scope's name in an operation's name stack: "jit(f)/repro.stage2/..." or,
# under a VJP, "transpose(jvp(repro.stage2))"
SCOPE = re.compile(r"repro\.[\w.-]*\w")
NO_SCOPE = "none"


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
    scopes = op_scopes(path)
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if is_device_plane(plane.name):
            scope = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["ops"][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns), scope.get(e.name, "")]
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    out["modules"][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def scope_path(tf_op: str) -> str:
    """The ``repro.*`` scopes in an operation's name stack, outermost first:
    ``jit(hop_fn)/repro.stage2/transpose(jvp(repro.moe))/dot`` ->
    ``repro.stage2/repro.moe``."""
    return "/".join(dict.fromkeys(SCOPE.findall(tf_op)))


def outermost(scope: str) -> str:
    return scope.split("/", 1)[0] or NO_SCOPE


def op_scopes(path: str) -> dict:
    """{device plane: {operation name: scope path}} from the ``tf_op`` stat
    of each operation's event metadata, which ``ProfileData`` does not
    expose."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: dict = {}
    for plane in space.planes:
        if not is_device_plane(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        scopes = out[plane.name] = {}
        for e in plane.event_metadata:
            for s in e.value.stats:
                if s.metadata_id in tf_op:  # the value, or a reference to a stat name holding it
                    scopes[e.value.name] = scope_path(
                        s.str_value or stat_names.get(s.ref_value, ""))
    return out


def _xspace_class():
    """A message class for the part of the profiler's ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``) read here, built by field
    number with the protobuf runtime alone: each plane's name and its event
    and stat metadata. What is not declared (lines, events) is skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto", package="bench_xspace",
                                            syntax="proto3")

    def message(name, *fields):
        """fields: (name, number, type: a scalar type or a message name, repeated)"""
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench_xspace." + ftype
            else:
                f.type = ftype

    message("XSpace", ("planes", 1, "XPlane", True))
    # map<int64, V> fields, read as the repeated (key, value) entries they are on the wire
    message("XPlane", ("name", 2, F.TYPE_STRING, False),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XEventMetadata", ("name", 2, F.TYPE_STRING, False), ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, False))
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_STRING, False), ("ref_value", 7, F.TYPE_UINT64, False))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


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


def innermost(spans: list, times: list) -> list:
    """The name of the innermost span open at each of the (sorted) times:
    of the spans open then, the one that started last ("no span" where none
    is). A span opens at its start and is closed from its end on."""
    order = sorted(spans, key=lambda h: (h[1], -h[2]))  # at one start, the outer first
    stack: list = []
    out, i = [], 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= t:
            stack.pop()  # a closed span below an open one waits until it is on top
        out.append(stack[-1][0] if stack else "no span")
    return out


def window_leaves(ev: dict, lo: float, hi: float):
    """(device, operation, start, duration, scope) of each leaf operation
    that starts inside [lo, hi)."""
    for d in sorted(ev["ops"]):
        for name, s, w, *scope in leaves(ev["ops"][d]):
            if lo <= s < hi:
                yield d, name, s, w, scope[0] if scope else ""


def scope_seconds(ev: dict, window_ns: list, scope: str) -> float:
    """Device seconds of the window's leaf operations that ran under
    ``scope`` at any depth (averaged over devices)."""
    total = sum(w for _, _, _, w, path in window_leaves(ev, *window_ns)
                if scope in path.split("/"))
    return total / len(ev["ops"]) * 1e-9


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and idle seconds (averaged over devices), per-program device
    seconds, the longest-running operations, the leaf operations' device
    seconds by outermost scope and the idle gaps by name, all inside the
    window span; times in seconds."""
    lo, hi = window_bounds(ev)
    devices = sorted(ev["ops"])
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = {d: clip(merge([[e[1], e[1] + e[2]] for e in ev["ops"][d]]), lo, hi)
            for d in devices}
    mods, mod_starts = {}, {}
    for d in devices:
        mods[d] = sorted(ev["modules"].get(d, []), key=lambda e: e[1])
        mod_starts[d] = [m[1] for m in mods[d]]
    ops: dict = defaultdict(float)
    scopes: dict = defaultdict(float)
    for d, name, s, w, scope in window_leaves(ev, lo, hi):
        i = bisect.bisect_right(mod_starts[d], s) - 1
        m = mods[d]
        mod = program(m[i][0]) if i >= 0 and s < m[i][1] + m[i][2] else "?"
        ops[f"{mod}/{op_name(name)}"] += w
        scopes[outermost(scope)] += w
    modules: dict = defaultdict(list)
    for d in ev["modules"]:
        for name, s, w in ev["modules"][d]:
            modules[program(name)].append([s, s + w])
    host = [h for h in ev["host"] if h[0] != WINDOW_SPAN]
    gaps: dict = defaultdict(float)
    for d in devices:
        edges = [lo] + [x for iv in busy[d] for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        names = innermost(host, [0.5 * (s + e) for s, e in idle])
        for name, (s, e) in zip(names, idle):
            gaps[name] += (e - s) / len(devices)
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
        "scopes_s": {k: v * ns / len(devices) for k, v in scopes.items()},
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
    for d, ops in ev["ops"].items():
        by_scope: dict = defaultdict(float)
        for _, _, w, scope in ops:
            by_scope[scope or NO_SCOPE] += w * 1e-9
        print(f"{d}: device seconds by scope {json.dumps(by_scope)}")
    spans: dict = defaultdict(int)
    for name, _, _ in ev["host"]:
        spans[name] += 1
    print(f"host spans: {json.dumps(spans)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
