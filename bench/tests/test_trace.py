"""The reduction from a profiler trace to busy time, program times,
operations and named idle gaps, on 30 ms recorded on a TPU v5e (ViT-S/16,
an adaptive ladder hop and the host's schedule refinement after it)."""
import json
from pathlib import Path

import pytest

from bench.harness import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_vit_hop.json"


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
