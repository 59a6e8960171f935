"""ExplainEngine: bucketing/masking correctness + compiled-executable cache.

The guarantees the serving refactor rests on:
  (a) mixed-length batches produce attributions identical to per-length
      unbatched calls — the padding mask changes nothing observable;
  (b) traffic at an already-seen bucket shape performs zero new compilations
      (counted by the engine's jit-wrapper compile counter);
  (c) every registry schedule keeps Σw == 1 and the completeness δ under
      masking, with exactly zero attribution at masked positions;
  (d) every attribution method in the MethodSpec registry serves through the
      engine — fixed-m AND adaptive — with zero steady-state recompiles
      (replayed traffic is pure cache hits), and the per-row compiled unit
      matches the core Explainer on the same embeddings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core import schedule
from repro.core.api import Explainer
from repro.core.baselines import pad_embedding
from repro.core.methods import METHODS
from repro.models.registry import Model
from repro.serve import ExplainEngine, ExplainRequest
from repro.serve.batching import bucket_for, plan_buckets, pow2_ladder

KEY = jax.random.PRNGKey(0)
MIXED_LENS = (9, 17, 24)


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


def _engine(cfg, params, **kw):
    kw.setdefault("schedule", "paper")
    kw.setdefault("m", 8)
    kw.setdefault("n_int", 4)
    return ExplainEngine(cfg, params, **kw)


# ------------------------------------------------------- (a) mask correctness


def test_mixed_length_matches_unbatched(lm):
    cfg, model, params = lm
    reqs = _requests(cfg, MIXED_LENS)
    mixed = _engine(cfg, params).explain(reqs)
    f = model.target_logprob_fn(params)
    for i, r in enumerate(reqs):
        # per-length unbatched engine call (same compiled path, B=1 bucket)
        single = _engine(cfg, params).explain([r])[0]
        np.testing.assert_allclose(
            mixed[i]["token_scores"], single["token_scores"], atol=1e-5
        )
        # exact-length jitted reference: no padding, no mask, fixed pos=-1
        e = model.embed_inputs(params, {"tokens": jnp.asarray(r.tokens)[None]})
        bl = pad_embedding(params["embed"]["embedding"], e, pad_id=0)
        ex = Explainer(f, schedule="paper", m=8, n_int=4)
        ref = jax.jit(ex.attribute)(e, bl, jnp.asarray([r.target]))
        np.testing.assert_allclose(
            mixed[i]["token_scores"],
            np.asarray(ref.attributions.sum(-1))[0],
            atol=1e-4,
        )
        np.testing.assert_allclose(mixed[i]["delta"], float(ref.delta[0]), atol=1e-4)


def test_masked_positions_exactly_zero(lm):
    cfg, _, params = lm
    reqs = _requests(cfg, MIXED_LENS, seed=1)
    out = _engine(cfg, params).explain(reqs, return_raw=True)
    for r, o in zip(reqs, out):
        raw = o["raw_token_scores"]
        assert raw.shape == (o["bucket"][1],)
        assert np.all(raw[len(r.tokens) :] == 0.0), "padding must attribute 0"
        assert np.isfinite(o["delta"])


@pytest.mark.parametrize("adaptive", [False, True])
def test_program_over_device_memory_compiles_with_remat(lm, monkeypatch, adaptive):
    """A program whose memory_analysis() exceeds the device's bytes_limit is
    compiled again with each layer recomputed in its backward pass, and
    serves what the engine that kept its residuals serves."""
    import dataclasses

    cfg = dataclasses.replace(lm[0], compute_dtype="float32")
    params = lm[2]
    kw = dict(adaptive=True, m=4, m_max=8) if adaptive else {}
    reqs = _requests(cfg, MIXED_LENS, seed=8)
    plain = _engine(cfg, params, **kw)
    ref = plain.explain(reqs)
    tight = _engine(cfg, params, **kw)
    monkeypatch.setattr(tight, "_memory_limit", lambda: 1)
    out = tight.explain(reqs)
    stats = [*tight.stats.buckets.values(), *tight.stats.hop_buckets.values()]
    assert stats and all(b.remat for b in stats)
    assert not any(b.remat for b in plain.stats.buckets.values())
    for eng, remat in ((tight, True), (plain, False)):
        for fn, sds, _ in eng._export_info.values():
            assert ("remat" in str(jax.make_jaxpr(fn)(*sds))) == remat
    for a, b in zip(out, ref):
        np.testing.assert_allclose(a["token_scores"], b["token_scores"], atol=1e-5)
        assert a.get("m_used") == b.get("m_used")


# ------------------------------------------------- (b) zero new compilations


def test_seen_bucket_never_recompiles(lm):
    cfg, _, params = lm
    eng = _engine(cfg, params)
    eng.explain(_requests(cfg, MIXED_LENS, seed=2))
    misses_after_warmup = eng.stats.misses
    assert misses_after_warmup == len(eng.stats.buckets) > 0
    # fresh requests, same shapes -> pure cache hits, zero compiles
    eng.explain(_requests(cfg, MIXED_LENS, seed=3))
    assert eng.stats.misses == misses_after_warmup
    assert eng.stats.hits == misses_after_warmup
    assert all(b.compiles == 1 for b in eng.stats.buckets.values())
    assert eng.stats.hit_rate == 0.5


# ------------------------------- (c) registry invariants under masking


def quad_f(xs, t):
    return jnp.sum(xs**2, axis=-1)


@pytest.mark.parametrize("name", sorted(schedule.SCHEDULES))
def test_registry_schedule_masked_invariants(name):
    x = jax.random.normal(KEY, (3, 8)) + 1.0
    bl = jnp.zeros_like(x)
    t = jnp.zeros((3,), jnp.int32)
    mask = jnp.asarray(np.tril(np.ones((3, 8), np.float32), k=4))  # ragged tail
    ex = Explainer(quad_f, schedule=name, m=16, n_int=4)
    sched = ex.build_schedule(x, bl, t, mask)
    np.testing.assert_allclose(np.asarray(sched.weights.sum(-1)), 1.0, rtol=1e-4)
    res = ex.attribute(x, bl, t, mask)
    attr = np.asarray(res.attributions)
    assert np.all(attr[np.asarray(mask) == 0.0] == 0.0)
    assert float(res.delta.max()) < 0.05
    # δ is over real tokens: completeness against f at the masked input
    masked_x = jnp.where(mask.astype(bool), x, bl)
    gap = np.abs(attr.sum(-1) - np.asarray(quad_f(masked_x, t) - quad_f(bl, t)))
    np.testing.assert_allclose(gap, np.asarray(res.delta), atol=1e-5)


# --------------------------------- (d) method zoo through the serving engine


@pytest.mark.parametrize("method", sorted(METHODS))
def test_method_zoo_zero_steady_state_recompiles(lm, method):
    """Acceptance gate: every registered method serves mixed-length traffic
    through the engine, and replaying fresh same-shape traffic touches only
    warmed executables (ensemble methods included — their noise is a pure
    function of the request indices, so the escalation path replays too)."""
    cfg, _, params = lm
    eng = _engine(cfg, params, method=method, n_samples=2)
    out = eng.explain(_requests(cfg, MIXED_LENS, seed=11))
    misses = eng.stats.misses
    assert misses > 0
    out2 = eng.explain(_requests(cfg, MIXED_LENS, seed=12))
    assert eng.stats.misses == misses, f"{method} recompiled at steady state"
    for o in out + out2:
        assert np.isfinite(o["token_scores"]).all()
        assert np.isfinite(o["delta"]) and np.isfinite(o["f_x"])
    for o, r in zip(out, _requests(cfg, MIXED_LENS, seed=11)):
        assert o["token_scores"].shape == (len(r.tokens),)


@pytest.mark.parametrize(
    "method", sorted(n for n in METHODS if not METHODS[n].forward_only)
)
def test_method_zoo_adaptive_zero_recompiles_on_replay(lm, method):
    cfg, _, params = lm
    reqs = _requests(cfg, (9, 17, 12, 24), seed=13)
    eng = _engine(
        cfg, params, method=method, m=4, adaptive=True, tol=1e-2, m_max=16,
        n_samples=2,
    )
    out = eng.explain(reqs)
    misses = eng.stats.misses
    out2 = eng.explain(reqs)
    assert eng.stats.misses == misses, f"{method} adaptive replay recompiled"
    for o, o2 in zip(out, out2):
        assert o["m_used"] in eng.m_ladder and o["hops"] >= 0
        np.testing.assert_array_equal(o["token_scores"], o2["token_scores"])


def test_engine_idgi_matches_core_explainer(lm):
    """The engine's compiled IDGI unit == the core Explainer on the same
    embeddings (the serving stack adds batching/masking, not math)."""
    cfg, model, params = lm
    (req,) = _requests(cfg, (9,), seed=14)
    out = _engine(cfg, params, method="idgi").explain([req])[0]
    f = model.target_logprob_fn(params)
    e = model.embed_inputs(params, {"tokens": jnp.asarray(req.tokens)[None]})
    bl = pad_embedding(params["embed"]["embedding"], e, pad_id=0)
    ex = Explainer(f, method="idgi", schedule="paper", m=8, n_int=4)
    ref = jax.jit(ex.attribute)(e, bl, jnp.asarray([req.target]))
    np.testing.assert_allclose(
        out["token_scores"], np.asarray(ref.attributions.sum(-1))[0], atol=1e-4
    )
    np.testing.assert_allclose(out["delta"], float(ref.delta[0]), atol=1e-4)


def test_ensemble_engine_result_is_sample_mean(lm):
    """n_samples=1 with sigma→0 degrades noise_tunnel to plain IG — the
    reduction plumbing must be exact in that corner."""
    cfg, _, params = lm
    reqs = _requests(cfg, MIXED_LENS, seed=15)
    nt = _engine(cfg, params, method="noise_tunnel", n_samples=1, sigma=1e-9)
    base = _engine(cfg, params, method="ig")
    out_nt = nt.explain(reqs)
    out_ig = base.explain(reqs)
    for a, b in zip(out_nt, out_ig):
        np.testing.assert_allclose(a["token_scores"], b["token_scores"], atol=1e-4)


# ----------------------------------------------------------- bucket planning


def test_bucket_planning():
    reqs = _requests(type("C", (), {"vocab_size": 64}), (3, 9, 9, 17, 100))
    plan = plan_buckets(reqs, seq_buckets=(8, 16, 32, 128), batch_buckets=(1, 2, 4))
    shapes = {bb.bucket for bb in plan}
    assert shapes == {(1, 8), (2, 16), (1, 32), (1, 128)}
    for bb in plan:
        assert bb.tokens.shape == bb.bucket and bb.mask.shape == bb.bucket
        for row in range(bb.bucket[0]):
            n = bb.lens[row]
            assert bb.mask[row, :n].all() and not bb.mask[row, n:].any()
    served = sorted(i for bb in plan for i in bb.indices)
    assert served == list(range(len(reqs)))


def test_bucket_planning_splits_beyond_batch_ladder():
    """More same-bucket rows than the batch ladder's top rung -> split, not crash."""
    reqs = _requests(type("C", (), {"vocab_size": 64}), (7,) * 9)
    plan = plan_buckets(reqs, seq_buckets=(8,), batch_buckets=(1, 2, 4))
    assert [bb.bucket for bb in plan] == [(4, 8), (4, 8), (1, 8)]
    served = sorted(i for bb in plan for i in bb.indices)
    assert served == list(range(9))


def test_ladders():
    assert pow2_ladder(100) == (8, 16, 32, 64, 128)
    assert bucket_for(9, (8, 16, 32)) == 16
    with pytest.raises(ValueError):
        bucket_for(64, (8, 16, 32))
