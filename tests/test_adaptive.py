"""Adaptive iso-convergence: resumable accumulation, nested refinement,
ladder escalation (DESIGN.md §7).

The guarantees under test:
  (a) escalation never discards or corrupts work — running the ladder to a
      rung is BIT-IDENTICAL to one fixed-m run over the materialized nested
      schedule at that rung (same chunking), for a causal LM through the
      serving engine and for a CNN through the core API;
  (b) per-example m_used / hops / convergence flags match a hand-computed
      trace of fixed-m runs over the refined schedules;
  (c) escalation only ever touches the warmed closed set of executables —
      replaying identical traffic performs zero new compilations;
  (d) the escalation batching helpers keep (B, S) on the ladder.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ig, schedule
from repro.core.api import Explainer
from repro.core.schedule import Schedule
from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CONFIG as CNN_CONFIG
from repro.models import cnn
from repro.models.registry import Model
from repro.serve import ExplainEngine, ExplainRequest
from repro.serve.batching import pad_rows

KEY = jax.random.PRNGKey(0)


def quad_f(xs, t):
    return jnp.sum(xs**2, axis=-1)


def _materialize_ladder(ex: Explainer, x, bl, t, hops: int) -> Schedule:
    """The nested schedule a full-ladder run lands on: base build + refines."""
    fam = schedule.family(ex.schedule)
    sched = ex.build_schedule(x, bl, t)
    a = jnp.broadcast_to(sched.alphas, (x.shape[0], sched.alphas.shape[-1]))
    w = jnp.broadcast_to(sched.weights, a.shape)
    sched = Schedule(a, w)
    for _ in range(hops):
        sched = fam.refine(sched)
    return sched


# ------------------------------------------------- (a) bit-identity, core


@pytest.mark.parametrize("schedule_name", ["uniform", "paper"])
def test_full_ladder_bit_identical_to_fixed_run(schedule_name):
    """tol=0 never converges -> every example rides the whole ladder; the
    result must equal one fixed run over the final nested schedule, bit for
    bit (old weights halve by exact power-of-two scaling and chunk
    boundaries align at every rung)."""

    def f(xs, t):  # curved enough that delta > 0 at every rung
        return jnp.tanh((xs**2).sum(-1) / 10.0)

    x = jax.random.normal(KEY, (3, 8)) + 1.0
    bl = jnp.zeros_like(x)
    t = jnp.zeros((3,), jnp.int32)
    ex = Explainer(f, schedule=schedule_name, m=4, n_int=2)
    res, info = ex.attribute_adaptive(x, bl, t, tol=0.0, m_max=16)
    assert list(info["m_used"]) == [16, 16, 16] and list(info["hops"]) == [2, 2, 2]
    assert not info["converged"].any()

    final = _materialize_ladder(ex, x, bl, t, hops=2)
    fixed = ig.attribute(f, x, bl, final, t, chunk=ex.adaptive_chunk)
    np.testing.assert_array_equal(
        np.asarray(res.attributions), np.asarray(fixed.attributions)
    )
    # δ reuses the rung-0 endpoint forwards, which this eager reference
    # recomputes — identical math, but eager-vs-compiled can differ by 1 ulp
    np.testing.assert_allclose(
        np.asarray(res.delta), np.asarray(fixed.delta), atol=1e-6
    )


def test_full_ladder_bit_identical_cnn():
    """Same guarantee on the paper CNN (conv stack, randomly initialized)."""
    params = cnn.init(CNN_CONFIG, KEY)
    f = lambda xs, t: cnn.prob_fn(CNN_CONFIG, params, xs, t)
    s = CNN_CONFIG.image_size
    x = jax.random.uniform(jax.random.fold_in(KEY, 1), (2, s, s, CNN_CONFIG.channels))
    bl = jnp.zeros_like(x)
    t = jnp.zeros((2,), jnp.int32)
    ex = Explainer(f, schedule="paper", m=4, n_int=2)
    res, info = ex.attribute_adaptive(x, bl, t, tol=0.0, m_max=8)
    assert list(info["m_used"]) == [8, 8]

    final = _materialize_ladder(ex, x, bl, t, hops=1)
    fixed = ig.attribute(f, x, bl, final, t, chunk=ex.adaptive_chunk)
    np.testing.assert_array_equal(
        np.asarray(res.attributions), np.asarray(fixed.attributions)
    )


# ------------------------------------------- (b) hand-computed trace, core


def test_m_used_and_hops_match_hand_trace():
    """Replay the ladder by hand with fixed-m runs over the refined
    schedules; the adaptive loop's per-example exit rungs must agree."""

    def f(xs, t):
        return jnp.tanh((xs**2).sum(-1) / 8.0)

    # spread of magnitudes -> examples converge at different rungs
    x = jax.random.normal(KEY, (4, 6)) * jnp.asarray([[0.4], [0.9], [1.4], [2.2]])
    bl = jnp.zeros_like(x)
    t = jnp.zeros((4,), jnp.int32)
    tol, m_max = 2e-3, 32
    ex = Explainer(f, schedule="paper", m=4, n_int=2)
    res, info = ex.attribute_adaptive(x, bl, t, tol=tol, m_max=m_max)

    ladder = schedule.m_ladder(4, m_max)
    fixed = {
        m: ig.attribute(
            f, x, bl, _materialize_ladder(ex, x, bl, t, hops=j), t,
            chunk=ex.adaptive_chunk,
        )
        for j, m in enumerate(ladder)
    }
    thr = tol * np.abs(np.asarray(res.f_x) - np.asarray(res.f_baseline))
    for b in range(4):
        exit_rung, exit_hops = ladder[-1], len(ladder) - 1
        for j, m in enumerate(ladder):
            if float(fixed[m].delta[b]) <= thr[b]:
                exit_rung, exit_hops = m, j
                break
        assert info["m_used"][b] == exit_rung, (b, info["m_used"], exit_rung)
        assert info["hops"][b] == exit_hops
        assert info["converged"][b] == (float(fixed[exit_rung].delta[b]) <= thr[b])
        # the example's final numbers are the rung-of-exit numbers
        np.testing.assert_array_equal(
            np.asarray(res.attributions)[b], np.asarray(fixed[exit_rung].attributions)[b]
        )
    assert info["total_steps"] == int(np.sum(info["m_used"]))
    # steady state: a second call against the same cache compiles nothing
    cache = {}
    ex.attribute_adaptive(x, bl, t, tol=tol, m_max=m_max, cache=cache)
    _, info2 = ex.attribute_adaptive(x, bl, t, tol=tol, m_max=m_max, cache=cache)
    assert info2["compiles"] == 0


# --------------------------------------------------- engine (causal LM)


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(ARCHS["llama3-8b"])
    model = Model(cfg)
    params = model.init(KEY)
    return cfg, model, params


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [
        ExplainRequest(
            tokens=rng.integers(1, cfg.vocab_size, s).astype(np.int32),
            target=int(rng.integers(0, cfg.vocab_size)),
        )
        for s in lens
    ]


def test_engine_full_ladder_bit_identical_lm(lm):
    """Serving-engine escalation (causal LM): full-ladder output equals a
    fixed run over the materialized nested schedule on the same bucket.

    The reduced LM runs in bfloat16, where eager-vs-compiled fusion
    differences are far above 1 ulp — so the fixed-run reference must ride
    the same compiled machinery. A fixed run over schedule S IS a single hop
    from a zero accumulator (state_scale·0 == 0), which reuses the engine's
    own hop code path at n_new = m_final.
    """
    cfg, model, params = lm
    reqs = _requests(cfg, (11, 9, 12, 10))  # one (4, 16) bucket
    eng = ExplainEngine(
        cfg, params, schedule="paper", m=4, n_int=4, adaptive=True, tol=0.0, m_max=16
    )
    out = eng.explain(reqs, return_raw=True)
    assert all(o["m_used"] == 16 and o["hops"] == 2 for o in out)

    from repro.serve.batching import plan_buckets

    bb = plan_buckets(
        reqs, seq_buckets=eng.seq_buckets, batch_buckets=eng.batch_buckets, pad_id=0
    )[0]
    args = eng._bucket_inputs(bb)
    embeds, baseline, aux, mask = args
    chunk = eng._explainer.adaptive_chunk
    start, _ = eng._executable(
        ("start", bb.bucket, "riemann", "paper", 4, 4, chunk, ()),
        eng.stats.bucket(bb.bucket),
        eng._start_fn_for(4),
        args,
    )
    res0, state0, sched = start(params, *args)
    fam = schedule.family("paper")
    for _ in range(2):
        sched = fam.refine(sched)
    zero_state = ig.IGState(
        jnp.zeros_like(state0.acc), state0.f_x, state0.f_baseline
    )
    fixed_args = (embeds, baseline, aux, mask, sched, zero_state)
    fixed_fn, _ = eng._executable(
        ("hop", bb.bucket, "riemann", 16, chunk, ()),
        eng.stats.hop_bucket(bb.bucket),
        eng._hop_fn_for(4),
        fixed_args,
    )
    fixed, _ = fixed_fn(params, *fixed_args)
    per_token = np.asarray(fixed.attributions.sum(-1))
    for row, o in enumerate(out):
        np.testing.assert_array_equal(o["raw_token_scores"], per_token[row])
        np.testing.assert_array_equal(
            np.float32(o["delta"]), np.float32(fixed.delta[row])
        )


def test_engine_adaptive_stats_and_results(lm):
    cfg, _, params = lm
    reqs = _requests(cfg, (9, 17, 24, 12), seed=3)
    eng = ExplainEngine(
        cfg, params, schedule="paper", m=8, n_int=4, adaptive=True, tol=1e-2, m_max=32
    )
    out = eng.explain(reqs)
    a = eng.stats.adaptive
    assert a.requests == len(reqs)
    assert a.total_steps == sum(o["m_used"] for o in out)
    assert a.converged == sum(o["converged"] for o in out)
    assert a.m_used == {
        m: sum(1 for o in out if o["m_used"] == m) for m in {o["m_used"] for o in out}
    }
    assert a.early_exits == sum(
        1 for o in out if o["converged"] and o["m_used"] < eng.m_ladder[-1]
    )
    for o in out:
        assert o["m_used"] in eng.m_ladder
        assert o["hops"] == eng.m_ladder.index(o["m_used"])
        assert o["converged"] == (o["delta"] <= o["threshold"])
        # engine never spends the full ladder on an already-converged request
        if o["m_used"] > eng.m_ladder[0]:
            assert o["hops"] >= 1


def test_engine_adaptive_zero_recompiles_on_replay(lm):
    """Identical traffic replays the identical escalation path -> every
    start and hop executable is a cache hit (the §7 zero-recompile gate)."""
    cfg, _, params = lm
    reqs = _requests(cfg, (9, 17, 24, 12, 9, 30), seed=5)
    eng = ExplainEngine(
        cfg, params, schedule="paper", m=8, n_int=4, adaptive=True, tol=5e-3, m_max=32
    )
    eng.explain(reqs)
    misses = eng.stats.misses
    assert misses == eng.stats.compiles  # plan buckets + hop buckets
    eng.explain(reqs)
    assert eng.stats.misses == misses, "replayed traffic must never recompile"


def test_engine_adaptive_matches_fixed_when_tol_loose(lm):
    """A huge tolerance converges everything at rung 0 -> identical numbers
    to the non-adaptive engine at m = base rung."""
    cfg, _, params = lm
    reqs = _requests(cfg, (9, 17), seed=7)
    ad = ExplainEngine(
        cfg, params, schedule="paper", m=8, n_int=4, adaptive=True, tol=1e6
    )
    fx = ExplainEngine(cfg, params, schedule="paper", m=8, n_int=4)
    out_a = ad.explain(reqs)
    out_f = fx.explain(reqs)
    for oa, of in zip(out_a, out_f):
        assert oa["m_used"] == 8 and oa["hops"] == 0 and oa["converged"]
        np.testing.assert_allclose(oa["token_scores"], of["token_scores"], atol=1e-6)
        np.testing.assert_allclose(oa["delta"], of["delta"], atol=1e-6)


# ------------------------------------------------------- (d) ladder helpers


def test_pad_rows_and_m_ladder():
    assert pad_rows([3, 5], (1, 2, 4)) == ([3, 5], 2)
    assert pad_rows([3, 5, 6], (1, 2, 4)) == ([3, 5, 6, 6], 4)
    assert pad_rows([1], None) == ([1], 1)
    assert schedule.m_ladder(8, 64) == (8, 16, 32, 64)
    assert schedule.m_ladder(8, 8) == (8,)
    assert schedule.m_ladder(8, 63) == (8, 16, 32)
    with pytest.raises(AssertionError):
        schedule.m_ladder(8, 4)
