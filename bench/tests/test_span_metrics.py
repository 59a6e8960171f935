"""The readers of the program's span counters and dispatch times, on stats
and records made by hand: each gives the value computed by hand in its
cells, and nothing elsewhere or on a program without the counters."""
import math
from types import SimpleNamespace

import pytest

from bench.harness import loop, spec
from bench.harness.measure import Ctx

STEP, WAIT, COMPILE = "repro.sched.step", "repro.engine.wait", "repro.engine.compile"
CALL, INPUTS, MASKS = "repro.engine.call", "repro.engine.inputs", "repro.engine.masks"
REFINE, GATHER, READBACK = "repro.ladder.refine", "repro.ladder.gather", "repro.ladder.readback"
NEW = ["host_ms_per_step.poisson", "host_ms_per_step.backlog", "host_ms_per_step.mamba2",
       "ladder_host_ms_per_hop", "input_prep_ms_per_call", "queue_wait_p50_ms",
       "setup_compile_s"]

BEFORE = {STEP: (10, 1.0), WAIT: (5, 0.5), COMPILE: (4, 7.5), CALL: (5, 0.6),
          INPUTS: (5, 0.05), MASKS: (5, 0.02), REFINE: (2, 0.01)}
AFTER = {STEP: (30, 3.0), WAIT: (25, 1.2), COMPILE: (4, 7.5), CALL: (25, 1.5),
         INPUTS: (25, 0.15), MASKS: (25, 0.07), REFINE: (6, 0.05), GATHER: (4, 0.02),
         READBACK: (8, 0.03)}
# in the window: 20 steps of 2.0 s, 0.7 s of it waiting for the device;
# 20 calls with 0.1 + 0.05 s of input preparation; 4 hops, 0.04 + 0.02 +
# 0.03 s of ladder host work; set-up compiled for 7.5 s


def stats(spans, hops=0):
    s = SimpleNamespace(adaptive=SimpleNamespace(hop_calls=hops))
    if spans is not None:
        s.spans = dict(spans)
    return s


def ticket(submitted, dispatched, status="done"):
    t = SimpleNamespace(status=status, result=None, submitted_s=submitted)
    if dispatched != "absent":
        t.dispatched_s = dispatched
    return t


def ctx_of(config, arrivals, before, after, records=()):
    win = loop.Window(10.0, 10.0, list(records), before, after, 0, 0, 0)
    return Ctx({"name": config}, None, {"arrivals": arrivals}, {}, win, 30.0)


CELLS = {  # cell: (configuration, arrivals)
    "vit-s16.occlusion.poisson": ("vit-s16", "open"),
    "vit-s16.ig-adaptive.backlog": ("vit-s16", "closed"),
    "mamba2-780m.ig-uniform-m16.backlog": ("mamba2-780m", "closed"),
}
EXPECTED = {  # metric: value where it is reported
    "host_ms_per_step.poisson": 1e3 * (2.0 - 0.7) / 20,
    "host_ms_per_step.backlog": 1e3 * (2.0 - 0.7) / 20,
    "host_ms_per_step.mamba2": 1e3 * (2.0 - 0.7) / 20,
    "ladder_host_ms_per_hop": 1e3 * (0.04 + 0.02 + 0.03) / 4,
    "input_prep_ms_per_call": 1e3 * (0.10 + 0.05) / 20,
    "queue_wait_p50_ms": 1e3 * 0.25,
    "setup_compile_s": 7.5,
}
RECORDS = [loop.Rec(i, 0.0, 0.0, ticket(1.0 * i, 1.0 * i + w))
           for i, w in enumerate([0.1, 0.25, 0.4, 0.2, 0.3])]


def test_benchmark_lists_the_span_metrics_in_their_cells():
    bm = spec.load_benchmark()
    for name in NEW:
        entry = spec.find(bm["per_layer"], name, "metric")
        assert entry["source"] == "program_counter"
        for cell in entry["workloads"]:
            assert entry["moves"] in {m["name"] for m in spec.cell_metrics(bm, cell, False)}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("name", NEW)
def test_span_readers_by_hand(name, cell):
    listed = cell in spec.find(spec.load_benchmark()["per_layer"], name, "metric")["workloads"]
    hops = 4 if cell == "vit-s16.ig-adaptive.backlog" else 0  # the one cell with a ladder
    ctx = ctx_of(*CELLS[cell], stats(BEFORE, hops=3), stats(AFTER, hops=3 + hops), RECORDS)
    got = spec.metric_reader(name).read(ctx)
    if listed:
        assert got == pytest.approx(EXPECTED[name])
    else:
        assert got is None
    # a program without the span counters or the dispatch time: nothing
    old = [loop.Rec(0, 0.0, 0.0, ticket(0.0, "absent"))]
    assert spec.metric_reader(name).read(
        ctx_of(*CELLS[cell], stats(None), stats(None), old)) is None


def test_ladder_reads_nothing_without_hops():
    ctx = ctx_of("vit-s16", "closed", stats(BEFORE, hops=3), stats(AFTER, hops=3))
    assert spec.metric_reader("ladder_host_ms_per_hop").read(ctx) is None


def test_queue_wait_counts_a_request_never_dispatched_as_infinite():
    recs = RECORDS[:2] + [loop.Rec(9, 0.0, 0.0, ticket(9.0, None, "rejected_backpressure"))
                          for _ in range(3)]
    ctx = ctx_of("vit-s16", "open", stats(BEFORE), stats(AFTER), recs)
    assert math.isinf(spec.metric_reader("queue_wait_p50_ms").read(ctx))
    # two of seven never dispatched: the median moves up one rank
    ctx = ctx_of("vit-s16", "open", stats(BEFORE), stats(AFTER), RECORDS + recs[2:4])
    assert spec.metric_reader("queue_wait_p50_ms").read(ctx) == pytest.approx(300.0)
