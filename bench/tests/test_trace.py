"""The reduction from a profiler trace to busy time, program times,
operations and named idle gaps, on 30 ms recorded on a TPU v5e (ViT-S/16,
an adaptive ladder hop and the host's schedule refinement after it)."""
import json
from pathlib import Path

import pytest

from bench.harness import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_vit_hop.json"
# the reduction of FIXTURE before spans nested and scopes were read
REDUCED_BEFORE = Path(__file__).parent / "fixtures" / "trace_vit_hop.reduced.json"


@pytest.fixture(scope="module")
def ev():
    with open(FIXTURE) as fh:
        return json.load(fh)


def union_length(intervals, lo, hi):
    """Busy time by a plain sweep over the sorted ends."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted([max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s if cur_e is not None else 0.0)


def test_busy_time_is_the_union_of_operations(ev):
    red = tr.reduce(ev)
    lo, hi = red["window_ns"]
    ops = next(iter(ev["ops"].values()))
    assert red["window_s"] == pytest.approx(0.030)
    assert red["busy_s"] == pytest.approx(union_length([[s, s + d] for _, s, d in ops], lo, hi) * 1e-9)
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] + sum(s for _, s in red["idle_gaps"]) == pytest.approx(red["window_s"])


def test_gaps_are_named_by_the_open_span(ev):
    gaps = dict(tr.reduce(ev)["idle_gaps"])
    assert set(gaps) == {"bench.step", "no span"}
    assert gaps["bench.step"] > gaps["no span"] > 0


def test_programs_and_leaf_operations(ev):
    red = tr.reduce(ev)
    assert max(red["modules_s"], key=red["modules_s"].get) == "jit_hop_fn"
    ops = red["device_ops"]
    assert len(ops) == 10 and all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert all(name.startswith("jit_hop_fn/%") for name, _ in ops)
    # a loop's event contains its body's events: only leaves are counted
    leaf_total = sum(d for _, s, d in tr.leaves(next(iter(ev["ops"].values()))))
    assert leaf_total * 1e-9 <= red["busy_s"] * 1.0000001


def test_reduction_reads_as_before(ev):
    with open(REDUCED_BEFORE) as fh:
        before = json.load(fh)
    red = tr.reduce(ev)
    for k in before:
        if k != "idle_gaps":  # the one field a nested span may rename
            assert red[k] == before[k], k
    # this recording holds the harness's spans alone: the gaps read as before too
    assert red["idle_gaps"] == before["idle_gaps"]


def test_scopes_sum_to_the_leaf_operations(ev):
    red = tr.reduce(ev)
    lo, hi = red["window_ns"]
    leaf = sum(w for _, _, _, w, _ in tr.window_leaves(ev, lo, hi)) / len(ev["ops"]) * 1e-9
    assert sum(red["scopes_s"].values()) == pytest.approx(leaf, rel=1e-12)
    assert red["scopes_s"] == {tr.NO_SCOPE: pytest.approx(leaf)}  # recorded without scopes


NESTED = [  # [name, start, duration]: one scheduler step, as the program nests its spans
    ["bench.step", 0, 100],
    ["repro.sched.step", 5, 90],
    ["repro.engine.call", 10, 60],
    ["repro.engine.wait", 20, 40],
    ["repro.sched.deliver", 75, 10],
    ["bench.submit", 110, 5],
]


@pytest.mark.parametrize("t,name", [
    (2, "bench.step"), (7, "repro.sched.step"), (15, "repro.engine.call"),
    (20, "repro.engine.wait"), (59.9, "repro.engine.wait"), (60, "repro.engine.call"),
    (72, "repro.sched.step"), (80, "repro.sched.deliver"), (97, "bench.step"),
    (105, "no span"), (112, "bench.submit"), (-1, "no span"),
])
def test_gaps_are_named_by_the_innermost_span(t, name):
    assert tr.innermost(NESTED, [t]) == [name]
    # the same from a list in another order, among other times
    times = sorted({t, 2, 50, 80, 112})
    assert dict(zip(times, tr.innermost(NESTED[::-1], times)))[t] == name


def test_innermost_of_spans_that_overlap_without_nesting():
    # a span that outlives the one it opened inside: the latest-opened open one
    spans = [["a", 0, 10], ["b", 5, 10], ["c", 6, 2]]
    assert tr.innermost(spans, [1, 5, 7, 9, 12, 16]) == ["a", "b", "c", "b", "b", "no span"]


def test_scope_paths():
    assert tr.scope_path("jit(hop_fn)/jit(main)/repro.stage2/while/body/dot_general") == \
        "repro.stage2"
    assert tr.scope_path("jit(f)/repro.stage2/transpose(jvp(repro.moe))/mul") == \
        "repro.stage2/repro.moe"
    assert tr.scope_path("jit(start_fn)/repro.probe/repro.probe/sin") == "repro.probe"
    assert tr.scope_path("jit(fwd)/convert_element_type") == ""
    assert tr.outermost("repro.stage2/repro.moe") == "repro.stage2"
    assert tr.outermost("") == tr.NO_SCOPE


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _int(number, n):
    return _varint(number << 3) + _varint(n)


def _bytes(number, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_op_scopes_from_the_xspace_wire_format(tmp_path):
    """An XSpace written field by field as xplane.proto numbers them: a stat
    named tf_op holds each operation's name stack, as a string or as a
    reference to a stat metadata name; other stats, lines and the host
    plane are skipped."""
    stack = "jit(hop_fn)/repro.stage2/transpose(jvp(repro.moe))/dot_general"
    stat_meta = [(7, "tf_op"), (8, "flops"), (9, stack)]
    event_meta = [
        (1, "%fusion.1 = f32[8]{0} fusion()", [_int(1, 8) + _int(4, 5),
                                                _int(1, 7) + _bytes(5, "jit(start_fn)/repro.probe/sin")]),
        (2, "%dot.2 = f32[8]{0} dot()", [_int(1, 7) + _int(7, 9)]),
        (3, "%copy.3 = f32[8]{0} copy()", [_int(1, 7) + _bytes(5, "jit(f)/copy")]),
        (4, "%x.4", []),
    ]

    def plane(name):
        body = _int(1, 3) + _bytes(2, name)
        event = _int(1, 1) + _int(2, 5) + _int(3, 10)
        body += _bytes(3, _bytes(2, tr.OPS_LINE) + _bytes(4, event))
        for k, n, stats in event_meta:
            meta = _int(1, k) + _bytes(2, n) + _bytes(4, n.split(" ")[0]) + \
                b"".join(_bytes(5, s) for s in stats)
            body += _bytes(4, _int(1, k) + _bytes(2, meta))
        for k, n in stat_meta:
            body += _bytes(5, _int(1, k) + _bytes(2, _int(1, k) + _bytes(2, n)))
        return body

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_bytes(1, plane("/device:TPU:0")) + _bytes(1, plane("/host:CPU")) +
                     _bytes(2, "an error"))
    assert tr.op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion()": "repro.probe",
        "%dot.2 = f32[8]{0} dot()": "repro.stage2/repro.moe",
        "%copy.3 = f32[8]{0} copy()": "",
    }}


def test_names():
    assert tr.program("jit_hop_fn(16805046587430909115)") == "jit_hop_fn"
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == "%fusion.12"
    assert tr.leaves([["a", 0, 10], ["b", 2, 3], ["c", 5, 1], ["d", 20, 1]]) == \
        [["b", 2, 3], ["c", 5, 1], ["d", 20, 1]]


def test_a_trace_without_device_operations_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    tr.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.load(tr.find_xplane(str(tmp_path)))
    names = {h[0] for h in ev["host"]}
    assert {tr.WINDOW_SPAN, "bench.step"} <= names
    assert tr.window_bounds(ev)[1] > tr.window_bounds(ev)[0]
    with pytest.raises(ValueError):
        tr.reduce(ev)  # the CPU has no device plane: no device metric from it
