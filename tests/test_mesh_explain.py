"""Mesh-sharded ExplainEngine parity (DESIGN.md §9).

The contract under test, on a forced 4-device CPU mesh:
  (a) sharded attributions match the single-device engine within tolerance
      for every attribution method × schedule family, fixed-m AND adaptive;
  (b) the adaptive escalation TRACE (per-request m_used / hops) is identical
      to single-device — δ reductions are device-local, so the mesh never
      changes a serving decision;
  (c) replayed traffic performs zero recompiles against the mesh-keyed
      executable cache, and mesh-divisible padding means the replication
      fallback (EngineStats.mesh_fallbacks) is never taken;
  (d) single-device and sharded executables coexist in one shared AOT cache
      (keys carry the mesh axis sizes).

This module needs ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
set before backend init; in the plain single-device tier-1 process every
test here skips (conftest must never force virtual devices — see its
docstring), and CI runs this file in its own mesh-parity process.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core import schedule
from repro.core.api import Explainer
from repro.core.methods import METHODS
from repro.models.registry import Model
from repro.serve import ExplainEngine, ExplainRequest
from repro.serve.batching import BucketBatch, pad_rows

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4",
)

KEY = jax.random.PRNGKey(0)
MIXED_LENS = (9, 12, 17)


@pytest.fixture(scope="module")
def lm():
    # f32 compute: with threaded Eigen contractions (the XLA:CPU default)
    # a bf16 forward rounds differently at a device's share of the batch
    # than at the whole batch (one bf16 ulp of a logit, ~3.5e-4 in f(x)).
    # The served bf16 dtype is held to the same claim in test_mesh_bf16.py,
    # in a child process with single-threaded contractions.
    cfg = dataclasses.replace(reduced(ARCHS["llama3-8b"]), compute_dtype="float32")
    model = Model(cfg)
    return cfg, model, model.init(KEY)


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_explain_mesh

    return make_explain_mesh(4, 1)


def _requests(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [
        ExplainRequest(
            tokens=rng.integers(1, cfg.vocab_size, s).astype(np.int32),
            target=int(rng.integers(0, cfg.vocab_size)),
        )
        for s in lens
    ]


def _pair(cfg, params, mesh, **kw):
    kw.setdefault("schedule", "paper")
    kw.setdefault("m", 8)
    kw.setdefault("n_int", 4)
    return (
        ExplainEngine(cfg, params, **kw),
        ExplainEngine(cfg, params, mesh=mesh, **kw),
    )


# ---------------------------------------------------- (a) fixed-m parity


@pytest.mark.parametrize("method", sorted(METHODS))
def test_fixed_m_parity_per_method(lm, mesh, method):
    cfg, _, params = lm
    single, sharded = _pair(cfg, params, mesh, method=method, n_samples=2)
    reqs = _requests(cfg, MIXED_LENS, seed=1)
    out_s, out_m = single.explain(reqs), sharded.explain(reqs)
    for a, b in zip(out_s, out_m):
        np.testing.assert_allclose(a["token_scores"], b["token_scores"], atol=2e-4)
        np.testing.assert_allclose(a["delta"], b["delta"], atol=2e-4)
    # (c) zero steady-state recompiles against the mesh-keyed cache
    misses = sharded.stats.misses
    out_m2 = sharded.explain(_requests(cfg, MIXED_LENS, seed=2))
    assert sharded.stats.misses == misses, f"{method} recompiled under mesh"
    assert sharded.stats.mesh_fallbacks == 0
    assert all(np.isfinite(o["token_scores"]).all() for o in out_m2)


@pytest.mark.parametrize("sched", sorted(schedule.SCHEDULES))
def test_fixed_m_parity_per_schedule(lm, mesh, sched):
    cfg, _, params = lm
    single, sharded = _pair(cfg, params, mesh, schedule=sched)
    reqs = _requests(cfg, (9, 17), seed=3)
    for a, b in zip(single.explain(reqs), sharded.explain(reqs)):
        np.testing.assert_allclose(a["token_scores"], b["token_scores"], atol=2e-4)
    assert sharded.stats.mesh_fallbacks == 0


# ------------------------------------- (b) adaptive trace bit-identity


@pytest.mark.parametrize(
    "method", sorted(n for n in METHODS if not METHODS[n].forward_only)
)
def test_adaptive_trace_identical_to_single_device(lm, mesh, method):
    cfg, _, params = lm
    single, sharded = _pair(
        cfg, params, mesh, method=method, m=4, adaptive=True, tol=1e-2,
        m_max=16, n_samples=2,
    )
    reqs = _requests(cfg, (9, 17, 12, 24), seed=4)
    out_s, out_m = single.explain(reqs), sharded.explain(reqs)
    for a, b in zip(out_s, out_m):
        # the serving DECISIONS must match exactly: same exit rung, same
        # hop count, same convergence verdict per request
        assert (a["m_used"], a["hops"], a["converged"]) == (
            b["m_used"], b["hops"], b["converged"],
        ), f"{method} escalation trace diverged under mesh"
        np.testing.assert_allclose(a["token_scores"], b["token_scores"], atol=2e-4)
    # replayed adaptive traffic touches only warmed (mesh-keyed) executables
    misses = sharded.stats.misses
    out_m2 = sharded.explain(reqs)
    assert sharded.stats.misses == misses, f"{method} adaptive replay recompiled"
    assert sharded.stats.mesh_fallbacks == 0
    for a, b in zip(out_m, out_m2):
        np.testing.assert_array_equal(a["token_scores"], b["token_scores"])


# --------------------------- (c) mesh-divisible padding, fallback counter


def test_buckets_padded_to_dp_multiple(lm, mesh):
    cfg, _, params = lm
    eng = ExplainEngine(cfg, params, m=4, n_int=2, mesh=mesh)
    assert eng.dp == 4
    eng.explain(_requests(cfg, (9,), seed=5))  # 1 request -> B must pad to 4
    assert set(eng.stats.buckets) == {(4, 16)}
    assert eng.stats.mesh_fallbacks == 0


def test_pad_rows_mesh_multiple():
    rows, B = pad_rows([0], (1, 2, 4, 8), multiple=4)
    assert (rows, B) == ([0, 0, 0, 0], 4)
    rows, B = pad_rows([0, 1, 2, 3, 4], (1, 2, 4, 8), multiple=4)
    assert B == 8 and rows[:5] == [0, 1, 2, 3, 4]
    # no ladder: plain round-up to the multiple
    assert pad_rows([0, 1, 2], None, multiple=4)[1] == 4


def test_indivisible_bucket_counts_fallback(lm, mesh):
    """A hand-built B=3 bucket (bypassing plan-time padding) must serve
    correctly but replicated — counted, warned, never silent."""
    cfg, _, params = lm
    eng = ExplainEngine(cfg, params, m=4, n_int=2, mesh=mesh)
    reqs = _requests(cfg, (5, 5, 5), seed=6)
    tokens = np.stack([np.pad(r.tokens, (0, 3)) for r in reqs]).astype(np.int32)
    bb = BucketBatch(
        bucket=(3, 8),
        indices=(0, 1, 2),
        tokens=tokens,
        lens=np.full((3,), 5, np.int32),
        targets=np.asarray([r.target for r in reqs], np.int32),
        mask=(tokens != 0).astype(np.float32),
    )
    with pytest.warns(UserWarning, match="does not divide dp"):
        res = eng._run_bucket(bb)
    assert eng.stats.mesh_fallbacks == 1
    assert np.isfinite(np.asarray(res.attributions)).all()


# ------------------------------ (d) one cache, mesh-keyed, entries coexist


def test_adaptive_cache_coexists_across_meshes(lm, mesh):
    """Explainer.attribute_adaptive: one shared AOT cache dict serves a
    single-device and a mesh-sharded explainer without collisions — the
    cache key carries the mesh axis sizes."""
    cfg, model, params = lm
    f = model.target_logprob_fn(params)
    reqs = _requests(cfg, (8, 8, 8, 8), seed=7)
    tokens = jnp.asarray(np.stack([r.tokens for r in reqs]))
    e = model.embed_inputs(params, {"tokens": tokens})
    from repro.core.baselines import pad_embedding

    bl = pad_embedding(params["embed"]["embedding"], e, pad_id=0)
    tgt = jnp.asarray([r.target for r in reqs])
    cache = {}
    kw = dict(schedule="paper", m=4, n_int=4)
    res1, info1 = Explainer(f, **kw).attribute_adaptive(e, bl, tgt, m_max=8, cache=cache)
    n1 = len(cache)
    assert n1 == info1["compiles"] > 0
    res2, info2 = Explainer(f, mesh=mesh, **kw).attribute_adaptive(
        e, bl, tgt, m_max=8, cache=cache
    )
    assert len(cache) == n1 + info2["compiles"] > n1, "mesh entries must not collide"
    # B=4 divides dp=4 and hops pad survivors to dp multiples: everything shards
    assert info2["mesh_fallbacks"] == 0
    np.testing.assert_allclose(
        np.asarray(res1.attributions), np.asarray(res2.attributions), atol=2e-4
    )
    np.testing.assert_array_equal(info1["m_used"], info2["m_used"])
    # replay on the warmed shared cache: zero compiles for both explainers
    _, i1 = Explainer(f, **kw).attribute_adaptive(e, bl, tgt, m_max=8, cache=cache)
    _, i2 = Explainer(f, mesh=mesh, **kw).attribute_adaptive(e, bl, tgt, m_max=8, cache=cache)
    assert i1["compiles"] == i2["compiles"] == 0


def test_sharded_executables_actually_shard(lm, mesh):
    """The compiled entries under a mesh carry resolved NamedShardings and
    their outputs land distributed over the data axis."""
    cfg, _, params = lm
    eng = ExplainEngine(cfg, params, m=4, n_int=2, mesh=mesh)
    out = eng.explain(_requests(cfg, MIXED_LENS, seed=8))
    assert out and all(np.isfinite(o["token_scores"]).all() for o in out)
    assert all(sh is not None for _, sh in eng._cache.values())
    from repro.sharding import dp_size, explain_arg_shardings, mesh_cache_key

    assert dp_size(mesh) == 4
    assert mesh_cache_key(mesh) == (("data", 4), ("model", 1))
    args = (np.zeros((8, 16, 4), np.float32), np.zeros((8, 16), np.float32))
    sh = explain_arg_shardings(mesh, args)
    assert sh[0].spec == jax.sharding.PartitionSpec("data", None, None)
    assert sh[1].spec == jax.sharding.PartitionSpec("data", None)
    assert explain_arg_shardings(mesh, (np.zeros((3, 2), np.float32),)) is None
