"""Serving-layer spans (``repro.serve.spans``): the always-on span counters
agree with the counters that already exist, tickets carry their dispatch
time, and a profiler trace shows the spans nested on the host timeline."""
import glob

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models.registry import Model
from repro.runtime.fault import FaultConfig
from repro.serve import ExplainEngine, ExplainRequest, MixedScheduler
from repro.serve import spans as sp

KEY = jax.random.PRNGKey(0)
ENGINES = {
    "adaptive": dict(m=4, n_int=2, adaptive=True, tol=1e-3, m_max=16),
    "occlusion": dict(method="occlusion", n_masks=8),
}


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(ARCHS["llama3-8b"])
    return cfg, Model(cfg).init(KEY)


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [
        ExplainRequest(
            tokens=rng.integers(1, cfg.vocab_size, s).astype(np.int32),
            target=int(rng.integers(0, cfg.vocab_size)),
        )
        for s in lens
    ]


def _sched(lm, kind):
    cfg, params = lm
    eng = ExplainEngine(cfg, params, seq_buckets=(8, 16), **ENGINES[kind])
    clock = iter(range(10**6))  # a clock that moves one tick per reading
    return MixedScheduler(
        eng, max_len=16, fault_cfg=FaultConfig(max_retries=1, backoff_base_s=0.0),
        time_fn=lambda: float(next(clock)),
    )


def _serve(sched, reqs) -> tuple[list, int]:
    tickets = [sched.submit(r) for r in reqs]
    worked = 0
    while sched.step():
        worked += 1
    return tickets, worked


def test_span_names_are_stable_and_prefixed():
    assert len(set(sp.NAMES)) == len(sp.NAMES)
    assert all(n.startswith("repro.") for n in sp.NAMES)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_span_counts_agree_with_engine_counters(lm, kind):
    sched = _sched(lm, kind)
    st = sched.engine.stats
    tickets, worked = _serve(sched, _requests(lm[0], (5, 7, 12, 3, 9)))
    assert all(t.status == "done" for t in tickets)
    n = lambda name: st.spans.get(name, (0, 0.0))[0]
    secs = lambda name: st.spans.get(name, (0, 0.0))[1]
    calls = sum(b.calls for d in (st.buckets, st.hop_buckets) for b in d.values())
    assert calls > 0 and n(sp.CALL) == calls == n(sp.WAIT)
    assert n(sp.STEP) == worked
    assert n(sp.SUBMIT) == len(tickets)
    # every compile is charged to the span: executables (the cache's misses)
    # and the buckets' prep programs
    assert st.prep_compiles > 0
    assert n(sp.COMPILE) == st.misses + st.prep_compiles
    # the same quantities as before: call time is BucketStats.total_s,
    # compile time is compile_s, each item feeds the straggler monitor
    total = sum(b.total_s for d in (st.buckets, st.hop_buckets) for b in d.values())
    compile_s = sum(b.compile_s for d in (st.buckets, st.hop_buckets) for b in d.values())
    assert secs(sp.CALL) == pytest.approx(total)
    assert secs(sp.COMPILE) == pytest.approx(compile_s + st.prep_compile_s)
    assert sched.monitor._step == n(sp.ITEM)
    # nesting: a step holds its call, a call its wait
    assert secs(sp.WAIT) <= secs(sp.CALL) <= secs(sp.STEP)
    assert n(sp.INPUTS) == sum(b.calls for b in st.buckets.values())
    if kind == "adaptive":
        assert st.adaptive.hop_calls > 0
        assert n(sp.REFINE) == st.adaptive.hop_calls == n(sp.GATHER)
        # read back once at each start and once after each hop
        assert n(sp.READBACK) == n(sp.INPUTS) + st.adaptive.hop_calls
        assert "repro.engine.masks" not in st.spans
    else:
        # the mask draw runs inside the prep program that INPUTS dispatches
        assert n(sp.INPUTS) == calls
        assert "repro.engine.masks" not in st.spans
        assert n(sp.REFINE) == n(sp.GATHER) == n(sp.READBACK) == 0
    for t in tickets:
        assert t.submitted_s < t.dispatched_s < t.finished_s
    assert set(st.spans) <= set(sp.NAMES)


def test_refused_and_cached_tickets_dispatch_times(lm):
    cfg, params = lm
    eng = ExplainEngine(cfg, params, m=4, n_int=2, seq_buckets=(8,), result_cache=True)
    sched = MixedScheduler(eng, max_len=16, max_queue=1)
    req, other = _requests(cfg, (5, 6))
    first = sched.submit(req)
    refused = sched.submit(other)  # the queue holds one: refused at the door
    sched.run_until_idle()
    replay = sched.submit(req)  # a result-cache hit, answered at submit
    assert refused.status == "rejected_backpressure" and refused.dispatched_s is None
    assert first.submitted_s <= first.dispatched_s <= first.finished_s
    assert replay.status == "done" and replay.dispatched_s == replay.submitted_s


def test_profiler_trace_nests_step_call_wait(lm, tmp_path):
    """One adaptive bucket under the profiler: its host plane holds
    ``repro.sched.step`` around ``repro.engine.call`` around
    ``repro.engine.wait``, nested in time."""
    from jax.profiler import ProfileData

    sched = _sched(lm, "adaptive")
    reqs = _requests(lm[0], (5, 7))
    _serve(sched, reqs)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(sched, reqs)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))

    def inside(inner, outer):
        return outer[0] <= inner[0] and inner[1] <= outer[1]

    steps, calls, waits = events[sp.STEP], events[sp.CALL], events[sp.WAIT]
    assert {s[2]["kind"] for s in steps} >= {"exp_flush", "exp_start", "hop"}
    assert all(any(inside(w, c) for c in calls) for w in waits)
    assert all(any(inside(c, s) for s in steps) for c in calls)
    assert len(waits) == len(calls) > 0
