"""The readers of device time under the program's scopes, on a trace made
by hand: each gives the value computed by hand in its cells, and nothing on
a run without a trace, in an open loop, or where its scope ran nothing."""
from types import SimpleNamespace

import pytest

from bench.harness import loop, spec, trace as tr
from bench.harness.measure import Ctx

DEV = "/device:TPU:0"
EVENTS = {  # ns; the window is [0, 1000)
    "host": [[tr.WINDOW_SPAN, 0.0, 1000.0]],
    "modules": {DEV: [
        ["jit_start_fn(11)", -100.0, 90.0],  # before the window: not a call of it
        ["jit_start_fn(11)", 0.0, 300.0],
        ["jit_refine(12)", 300.0, 10.0],  # not a gradient program
        ["jit_hop_fn(13)", 400.0, 500.0],
        ["jit_hop_fn(13)", 950.0, 250.0],  # starts inside, ends after
    ]},
    "ops": {DEV: [
        ["%g", -50.0, 20.0, "repro.stage2"],
        ["%a", 10.0, 50.0, "repro.probe"],
        ["%b", 100.0, 150.0, "repro.stage2"],
        ["%while", 260.0, 30.0, "repro.stage2"],  # a loop: its body's operation is the leaf
        ["%c", 265.0, 10.0, "repro.stage2/repro.layer"],
        ["%f", 302.0, 5.0, ""],
        ["%d", 400.0, 400.0, "repro.stage2"],
        ["%e", 950.0, 100.0, "repro.stage2"],
    ]},
}
STAGE2_S, PROBE_S, NONE_S = 660e-9, 50e-9, 5e-9  # leaves starting in the window
GRAD_CALLS = 3
DONE = 4  # explanations answered in the window
PEAK = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}


def ctx_of(arrivals="closed", trace=True):
    recs = [loop.Rec(i, 0.0, 0.0, SimpleNamespace(status="done"), finish=1.0 + i)
            for i in range(DONE)]
    recs.append(loop.Rec(9, 0.0, 0.0, SimpleNamespace(status="queued")))  # still in flight
    win = loop.Window(10.0, 10.0, recs, None, None, 0, 0, 0)
    ctx = Ctx({}, None, {"arrivals": arrivals}, PEAK, win, 30.0)
    if trace:
        ctx.events, ctx.trace = EVENTS, tr.reduce(EVENTS)
    return ctx


def test_scopes_by_hand():
    red = tr.reduce(EVENTS)
    assert red["scopes_s"] == pytest.approx(
        {"repro.stage2": STAGE2_S, "repro.probe": PROBE_S, tr.NO_SCOPE: NONE_S})
    scope = spec.metric_reader("_scope")
    ctx = ctx_of()
    assert scope.seconds(ctx, "repro.stage2") == pytest.approx(STAGE2_S)
    assert scope.seconds(ctx, "repro.layer") == pytest.approx(10e-9)  # nested: at any depth
    assert scope.seconds(ctx, "repro.perturb") is None
    assert scope.calls(ctx, spec.metric_reader("_grad_roofline").is_grad_program) == GRAD_CALLS
    # 2 GFLOP at 100 TFLOP/s against 1 kB at 1 TB/s: FLOPs bound, 20 us over 660 ns
    assert scope.roofline(ctx, "repro.stage2", 2e9, 1e3) == pytest.approx(
        100.0 * 2e9 / 100e12 / STAGE2_S)
    assert scope.roofline(ctx, "repro.stage2", 0.0, 1e3) == pytest.approx(100.0 * 1e-9 / STAGE2_S)
    assert scope.roofline(ctx, "repro.perturb", 2e9, 1e3) is None
    assert scope.seconds(ctx_of(trace=False), "repro.stage2") is None


EXPECTED = {
    "probe_ms_per_expl.backlog": 1e3 * PROBE_S / DONE,
    "stage2_ms_per_call.backlog": 1e3 * STAGE2_S / GRAD_CALLS,
    "stage2_ms_per_call.mamba2": 1e3 * STAGE2_S / GRAD_CALLS,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_scope_readers_by_hand(name):
    read = spec.metric_reader(name).read
    assert read(ctx_of()) == pytest.approx(EXPECTED[name])
    assert read(ctx_of(trace=False)) is None
    assert read(ctx_of("open")) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_benchmark_lists_the_scope_metrics_in_their_cells(name):
    bm = spec.load_benchmark()
    entry = spec.find(bm["per_layer"], name, "metric")
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    for cell in entry["workloads"]:
        assert entry["moves"] in {m["name"] for m in spec.cell_metrics(bm, cell, False)}
        assert spec.cell(bm, cell)["traffic"]["method"] != "occlusion"
