"""MixedScheduler: unified generate+explain serving (ISSUE 8).

Covers the serving-path contracts the mixed gate
(benchmarks/mixed_serving.py) enforces at benchmark scale:

  * donated-endpoint bit-identity with the standalone engine, including
    identical adaptive ``m_used``/``hops``/``converged`` traces;
  * admission control: backpressure, tenant rate limits, poisoned-size
    degradation at submit time;
  * fault injection degrades ONLY the affected requests and the loop keeps
    serving; decode failures keep the emitted prefix; hop failures fall
    back to the last completed rung;
  * δ-aware preemption: queued escalation hops never delay decode;
  * streamed attributions arrive position-ordered and one-per-token.

Everything runs at float32 compute — the donation contract's bit-exact
regime (docs/serving.md). On XLA:CPU that regime also needs single-threaded
Eigen contractions: the threaded contraction partitions its sums by output
shape, and the decode prefill and the probe forward run different shapes,
so their f(x) can differ in the last bits. The bit-identity test therefore
runs in its own process with ``--xla_cpu_multi_thread_eigen=false``.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models.registry import Model
from repro.runtime.fault import FaultConfig
from repro.serve import (
    INTERACTIVE,
    ExplainEngine,
    ExplainRequest,
    GenerateRequest,
    MixedScheduler,
    TenantPolicy,
)

KEY = jax.random.PRNGKey(0)
RNG = np.random.default_rng(0)


def _prompt(n):
    return RNG.integers(1, 512, n).astype(np.int32)


def _setup():
    cfg = dataclasses.replace(
        reduced(ARCHS["llama3-8b"]), compute_dtype="float32"
    )
    model = Model(cfg)
    params = model.init(KEY)
    engine = ExplainEngine(
        cfg, params, m=4, n_int=2, seq_buckets=(8, 16),
        adaptive=True, tol=1e-3, m_max=8,
    )
    return cfg, params, engine


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _sched(engine, **kw):
    kw.setdefault("max_len", 16)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("fault_cfg", FaultConfig(max_retries=1, backoff_base_s=0.0))
    return MixedScheduler(engine, **kw)


def test_donated_endpoint_bit_identical(run_child):
    """Decode-path probe == standalone ExplainEngine probe, bit for bit,
    with identical adaptive escalation traces (single-threaded Eigen: see
    the module docstring)."""
    run_child(
        "import test_scheduler as t; t._check_donated_bit_identical(t._setup()[2])",
        "--xla_cpu_multi_thread_eigen=false",
    )


def _check_donated_bit_identical(engine):
    sched = _sched(engine)
    prompts = [_prompt(6), _prompt(7)]
    tickets = [
        sched.submit(GenerateRequest(tokens=p, num_tokens=2, explain=True))
        for p in prompts
    ]
    sched.run_until_idle()
    assert all(t.status == "done" for t in tickets)
    ref = engine.explain([
        ExplainRequest(tokens=p, target=int(t.tokens[0]))
        for p, t in zip(prompts, tickets)
    ])
    for t, r in zip(tickets, ref):
        got = next(a for a in t.attributions if a["pos"] == 0)
        np.testing.assert_array_equal(got["token_scores"], r["token_scores"])
        assert got["delta"] == r["delta"]
        assert got["f_x"] == r["f_x"]
        assert got["f_baseline"] == r["f_baseline"]
        # the scheduled ladder escalates identically to the inline one
        assert (got["m_used"], got["hops"], got["converged"]) == (
            r["m_used"], r["hops"], r["converged"],
        )
        assert not got["degraded"]


def test_streamed_attributions_position_ordered(setup):
    _, _, engine = setup
    sched = _sched(engine)
    t = sched.submit(GenerateRequest(
        tokens=_prompt(6), num_tokens=3, explain=True, explain_stream=True,
    ))
    sched.run_until_idle()
    assert t.status == "done"
    assert t.tokens.shape == (3,)
    assert [a["pos"] for a in t.attributions] == [0, 1, 2]
    for a in t.attributions:
        assert a["token"] == int(t.tokens[a["pos"]])
        # position k attributes prompt + k emitted prefix tokens
        assert a["token_scores"].shape == (6 + a["pos"],)
        assert np.isfinite(a["token_scores"]).all()


def test_fault_degrades_only_affected_bucket(setup):
    """A poisoned explain bucket degrades its own requests to the zero-score
    fallback; co-scheduled requests in other buckets are untouched and the
    loop keeps serving afterwards."""
    _, _, engine = setup
    sched = _sched(engine)
    healthy = [sched.submit(ExplainRequest(tokens=_prompt(6), target=3))
               for _ in range(2)]
    poisoned = sched.submit(ExplainRequest(tokens=_prompt(12), target=3))

    def hook(kind, payload):
        if kind in ("exp_start", "hop", "exp_fixed"):
            bucket = payload.bb.bucket if hasattr(payload, "bb") else payload.bucket
            if bucket[1] == 16:
                raise RuntimeError("injected poison")

    degraded0 = engine.stats.degraded
    sched.fault_hook = hook
    sched.run_until_idle()
    sched.fault_hook = None
    assert poisoned.status == "degraded" and poisoned.degraded
    assert poisoned.result["degraded"]
    np.testing.assert_array_equal(
        poisoned.result["token_scores"], np.zeros(12, np.float32)
    )
    assert engine.stats.degraded > degraded0
    for t in healthy:
        assert t.status == "done" and not t.degraded
        assert np.isfinite(t.result["token_scores"]).all()
    # the engine survived: the same scheduler serves the next request
    again = sched.submit(ExplainRequest(tokens=_prompt(12), target=3))
    sched.run_until_idle()
    assert again.status == "done"


def test_decode_failure_keeps_emitted_prefix(setup):
    _, _, engine = setup
    sched = _sched(engine)
    t = sched.submit(GenerateRequest(tokens=_prompt(6), num_tokens=4))

    def hook(kind, payload):
        if kind == "decode":
            raise RuntimeError("injected decode fault")

    sched.fault_hook = hook
    sched.run_until_idle()
    sched.fault_hook = None
    assert t.status == "degraded"
    # the prefill token was emitted before the decode stream died
    assert t.tokens.shape == (1,)


def test_hop_failure_falls_back_to_completed_rung(setup):
    """An escalation-hop fault degrades the still-active rows to their
    rung-0 attributions — complete, finite, just less converged."""
    _, _, engine = setup
    sched = _sched(engine)
    t = sched.submit(ExplainRequest(tokens=_prompt(6), target=3))

    def hook(kind, payload):
        if kind == "hop":
            raise RuntimeError("injected hop fault")

    sched.fault_hook = hook
    sched.run_until_idle()
    sched.fault_hook = None
    assert t.status == "degraded"
    r = t.result
    assert r["degraded"] and not r["converged"]
    assert r["m_used"] == engine.m and r["hops"] == 0
    assert np.isfinite(r["token_scores"]).all()
    assert np.abs(r["token_scores"]).sum() > 0  # rung 0 stood, not zeroed


def test_hops_are_preempted_by_decode(setup):
    """With escalation hops queued, a newly admitted interactive generate
    dispatches ahead of them and the deferral is counted."""
    _, _, engine = setup
    sched = _sched(engine)
    preempted0 = engine.stats.preempted
    sched.submit(ExplainRequest(tokens=_prompt(6), target=3))
    while not any(k == "hop" for _, _, k, _ in sched._heap):
        assert sched.step(), "ladder converged before any hop was queued"
    t = sched.submit(GenerateRequest(
        tokens=_prompt(7), num_tokens=2, slo=INTERACTIVE,
    ))
    sched.run_until_idle()
    assert t.status == "done"
    assert engine.stats.preempted > preempted0


def test_backpressure_rejects_above_max_queue(setup):
    _, _, engine = setup
    sched = _sched(engine, max_queue=1)
    t1 = sched.submit(GenerateRequest(tokens=_prompt(6), num_tokens=1))
    t2 = sched.submit(GenerateRequest(tokens=_prompt(6), num_tokens=1))
    assert t1.status == "queued"
    assert t2.status == "rejected_backpressure"
    assert sched.rejected_backpressure == 1
    sched.run_until_idle()
    assert t1.status == "done"


def test_tenant_rate_limit(setup):
    _, _, engine = setup
    sched = _sched(engine, tenants={"default": TenantPolicy(rate=0.0, burst=1)})
    t1 = sched.submit(ExplainRequest(tokens=_prompt(6), target=1))
    t2 = sched.submit(ExplainRequest(tokens=_prompt(6), target=1))
    assert t1.status == "queued"
    assert t2.status == "rejected_rate"
    assert sched.rejected_rate == 1


def test_poisoned_size_degrades_at_admission(setup):
    """A prompt no bucket or the KV cache can hold must degrade at submit
    time instead of reaching (and killing) the dispatch loop."""
    _, _, engine = setup
    sched = _sched(engine)
    too_long = sched.submit(ExplainRequest(tokens=_prompt(64), target=1))
    assert too_long.status == "degraded"
    overflow = sched.submit(GenerateRequest(tokens=_prompt(12), num_tokens=8))
    assert overflow.status == "degraded"  # 12 + 8 > max_len=16
    assert overflow.tokens.shape == (0,)
    sched.run_until_idle()  # nothing queued explodes


def test_num_tokens_zero_completes_empty(setup):
    _, _, engine = setup
    sched = _sched(engine)
    t = sched.submit(GenerateRequest(tokens=_prompt(6), num_tokens=0))
    assert t.status == "done"
    assert t.tokens.shape == (0,)


def test_zero_steady_state_recompiles(setup):
    """Replaying an identical mixed workload reuses every executable —
    decode and explain are one combined compile set."""
    _, _, engine = setup
    sched = _sched(engine)

    def workload():
        ts = [
            sched.submit(GenerateRequest(tokens=_prompt(6), num_tokens=2,
                                         explain=True)),
            sched.submit(ExplainRequest(tokens=_prompt(7), target=5)),
        ]
        sched.run_until_idle()
        return ts

    workload()
    misses0 = engine.stats.misses
    ts = workload()
    assert engine.stats.misses == misses0
    assert all(t.status == "done" for t in ts)
