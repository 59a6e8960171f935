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
      matches the core Explainer on the same embeddings;
  (e) each bucket's compiled prep program returns the arguments the eager
      per-op composition built, compiles once per argument shape, and draws
      per-row samples that do not depend on the bucket's padding.
"""
import copy
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.vit import reduced_vit
from repro.core import perturb, schedule
from repro.core.api import Explainer
from repro.core.baselines import pad_embedding
from repro.core.methods import METHODS
from repro.models.registry import Model, model_for
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
    prep = eng.stats.prep_compiles
    out2 = eng.explain(_requests(cfg, MIXED_LENS, seed=12))
    assert eng.stats.misses == misses, f"{method} recompiled at steady state"
    assert eng.stats.prep_compiles == prep > 0
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


# ----------------------------------------------- (e) the bucket prep program


def _eager_bucket_inputs(eng, bb):
    """The arguments as the engine built them before the prep program: one
    eager device op at a time (the oracle the compiled prep must match)."""
    tokens = jnp.asarray(bb.tokens)
    aux = {
        "target": jnp.asarray(bb.targets, jnp.int32),
        "pos": jnp.asarray(bb.lens - 1, jnp.int32),
    }
    mask = jnp.asarray(bb.mask)
    if bb.features is not None:
        feats = jnp.asarray(bb.features)
        embeds = eng.model.embed_features(eng.params, feats)
        baseline = eng.model.embed_features(eng.params, jnp.zeros_like(feats))
    else:
        embeds = eng.model.embed_inputs(eng.params, {"tokens": tokens})
        baseline = pad_embedding(
            eng.params["embed"]["embedding"], embeds, pad_id=eng.pad_id
        )
    padded = list(bb.indices)
    padded += [padded[-1]] * (bb.bucket[0] - len(padded))
    S = bb.bucket[1]
    keys = jax.vmap(lambda i: perturb.request_key(eng.sample_seed, S, i))(
        jnp.asarray(padded, jnp.uint32)
    )
    if eng._spec.expand is not None:
        e2, b2 = jax.vmap(
            lambda e, b, k: eng._spec.expand(e[None], b[None], k, 1, eng.sigma)
        )(embeds, baseline, keys)
        embeds, baseline = e2[:, 0], b2[:, 0]
    args = (embeds, baseline, aux, mask)
    if eng._spec.forward_only:
        pm = perturb.draw_masks(eng._spec.name, keys, S, eng.n_masks)
        return args + ((pm.z,) if pm.groups is None else (pm.z, pm.groups))
    if bb.f_x is not None:
        return args + (jnp.asarray(bb.f_x, jnp.float32),)
    return args


PREP_PATHS = {
    # name: (model, method, donated f_x)
    "tokens": ("lm", "ig", False),
    "tokens-fx": ("lm", "ig", True),
    "features": ("vit", "ig", False),
    "features-bf16": ("vit-bf16", "idgi", False),
    "noise_tunnel": ("lm", "noise_tunnel", False),
    "expected_grad": ("vit", "expected_grad", False),
    "occlusion": ("vit", "occlusion", False),
    "rise": ("lm", "rise", False),
    "lime": ("lm", "lime", False),
}


@pytest.fixture(scope="module")
def models(lm):
    vit = reduced_vit()
    vit16 = replace(vit, compute_dtype="bfloat16")
    vit_params = model_for(vit).init(KEY)
    return {"lm": lm[::2], "vit": (vit, vit_params), "vit-bf16": (vit16, vit_params)}


def _prep_requests(cfg, lens, *, f_x=False, seed=0):
    rng = np.random.default_rng(seed)
    vit = hasattr(cfg, "patch_dim")
    return [
        ExplainRequest(
            tokens=(np.arange(s) if vit else rng.integers(1, cfg.vocab_size, s))
            .astype(np.int32),
            target=int(rng.integers(0, cfg.num_classes if vit else cfg.vocab_size)),
            features=(
                rng.normal(size=(s, cfg.patch_dim)).astype(np.float32) if vit else None
            ),
            f_x=-1.25 - s if f_x else None,
        )
        for s in lens
    ]


def _prep_bucket(models, path, **plan_kw):
    model, method, f_x = PREP_PATHS[path]
    cfg, params = models[model]
    eng = _engine(cfg, params, method=method, seq_buckets=(8, 16), sample_seed=5)
    reqs = _prep_requests(cfg, (13, 9, 11), f_x=f_x)  # 3 rows -> B=4: one pad row
    (bb,) = plan_buckets(reqs, seq_buckets=eng.seq_buckets, **plan_kw)
    return eng, bb


@pytest.mark.parametrize("path", sorted(PREP_PATHS))
def test_prep_program_matches_eager_composition(models, path):
    """The compiled prep returns the eager composition's arguments, leaf for
    leaf and bit for bit. A path ensemble's noisy rows are the one exception:
    the compiled ``x + σ·n`` may fuse into one rounding where eager rounds
    twice, so they agree to that rounding (the random draws are the same)."""
    eng, bb = _prep_bucket(models, path)
    assert bb.bucket == (4, 16)
    got = jax.tree.leaves(eng._bucket_inputs(bb))
    want = jax.tree.leaves(_eager_bucket_inputs(eng, bb))
    assert len(got) == len(want)
    noisy = {"noise_tunnel": 0, "expected_grad": 1}.get(path)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.shape == w.shape and g.dtype == w.dtype, i
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if i == noisy:
            eps = float(jnp.finfo(eng.model.cfg.compute_dtype).eps)
            quiet = copy.copy(eng)  # the same engine drawing no noise
            quiet.sigma = 0.0
            quiet._prep_cache = {}  # its programs would draw the noise
            x = np.asarray(jax.tree.leaves(_eager_bucket_inputs(quiet, bb))[i], np.float64)
            bound = 4 * eps * (np.abs(w) + np.abs(w - x))
            assert np.all(np.abs(g - w) <= bound), i
            assert not np.array_equal(w, x)  # the noise was drawn
        else:
            np.testing.assert_array_equal(g, w, err_msg=f"leaf {i}")
    assert eng.stats.prep_compiles == 1


@pytest.mark.parametrize("path", ["tokens", "features", "occlusion", "lime"])
def test_prep_second_pass_compiles_nothing(models, path):
    """One prep program per argument shape: a second pass over the same
    buckets reuses them, and each compile is charged to the compile span
    and to the prep counters, never to the executable cache or a bucket."""
    model, method, _ = PREP_PATHS[path]
    cfg, params = models[model]
    eng = _engine(cfg, params, method=method, seq_buckets=(8, 16))
    plan = plan_buckets(
        _prep_requests(cfg, (3, 5, 11, 9, 16, 7)), seq_buckets=eng.seq_buckets,
        batch_buckets=eng.batch_buckets,
    )
    shapes = {bb.bucket for bb in plan}
    assert len(shapes) > 1
    first = [eng._bucket_inputs(bb) for bb in plan]
    assert eng.stats.prep_compiles == len(shapes)
    assert eng.stats.spans["repro.engine.compile"][0] == len(shapes)
    assert eng.stats.misses == eng.stats.hits == 0 and not eng.stats.buckets
    assert eng.stats.prep_compile_s == pytest.approx(
        eng.stats.spans["repro.engine.compile"][1]
    )
    second = [eng._bucket_inputs(bb) for bb in plan]
    assert eng.stats.prep_compiles == len(shapes)
    assert eng.stats.spans["repro.engine.inputs"][0] == 2 * len(plan)
    for a, b in zip(first, second):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("path", ["noise_tunnel", "occlusion", "rise", "lime"])
def test_prep_mesh_padded_bucket_draws_same_rows(models, path):
    """A bucket padded up to a data-parallel multiple (B 4 -> 8) draws the
    same per-row masks and noise for its real rows as the unpadded one."""
    eng, bb = _prep_bucket(models, path)
    _, wide = _prep_bucket(models, path, batch_multiple=8)
    assert (bb.bucket, wide.bucket) == ((4, 16), (8, 16))
    n = len(bb.indices)
    got = jax.tree.leaves(eng._bucket_inputs(wide))
    want = jax.tree.leaves(eng._bucket_inputs(bb))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[:n], np.asarray(w)[:n])
    # pad rows repeat the last real row's draw
    for g in got:
        g = np.asarray(g)
        for row in range(n, wide.bucket[0]):
            np.testing.assert_array_equal(g[row], g[n - 1])
    assert eng.stats.prep_compiles == 2


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
