"""Content-addressed attribution cache + warm-start persistence (ISSUE 10).

The contracts, stated as tests:

  (a) key sensitivity — flipping ANY keyed knob (method, schedule family,
      m, sample seed, baseline id, model params, attention impl, mesh,
      fused) changes ``request_cache_key``; the identical engine + request
      reproduces the identical key; different request bytes never collide;
  (b) replay — a hit is ``np.array_equal`` to the fresh computation, and a
      caller mutating a hit can never corrupt the stored bytes;
  (c) eviction — the LRU byte budget holds after every put, oversize
      entries are refused, counters track hits/misses/evictions;
  (d) warm-start — save/restore round-trips the executable set with ZERO
      compiles on replay; a corrupted shard, a truncated manifest, or an
      engine-context mismatch falls back COLD (warn, never raise, never
      wrong results);
  (e) scheduler admission — a cached explain request completes AT submit
      with no queue slot; only degraded results are never cached.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.serve import ExplainEngine, ExplainRequest, ResultCache
from repro.serve.result_cache import _entry_bytes
from repro.serve.warm_state import load_warm_state, save_warm_state

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def lm():
    from repro.configs import ARCHS, reduced
    from repro.models.registry import Model

    cfg = dataclasses.replace(
        reduced(ARCHS["llama3-8b"]), compute_dtype="float32"
    )
    model = Model(cfg)
    return cfg, model, model.init(KEY)


def _req(cfg, n=7, seed=0, target=3):
    rng = np.random.default_rng(seed)
    return ExplainRequest(
        tokens=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
        target=target,
    )


def _engine(cfg, params, **kw):
    kw.setdefault("m", 4)
    kw.setdefault("n_int", 2)
    kw.setdefault("seq_buckets", (8, 16))
    return ExplainEngine(cfg, params, **kw)


# ------------------------------------------------------- (a) key sensitivity


def test_key_is_deterministic_and_request_sensitive(lm):
    cfg, _, params = lm
    req = _req(cfg)
    k1 = _engine(cfg, params).request_cache_key(req)
    k2 = _engine(cfg, params).request_cache_key(req)
    assert k1 == k2, "same engine identity + request must reproduce the key"
    assert _engine(cfg, params).request_cache_key(_req(cfg, seed=1)) != k1
    assert _engine(cfg, params).request_cache_key(_req(cfg, target=5)) != k1
    assert _engine(cfg, params).request_cache_key(_req(cfg, n=9)) != k1


def test_key_sensitivity_matrix(lm):
    """Every knob the docs/caching.md contract lists must move the key."""
    cfg, model, params = lm
    req = _req(cfg)
    base = _engine(cfg, params).request_cache_key(req)
    variants = {
        "method": dict(method="idgi"),
        "schedule": dict(schedule="uniform"),
        "m": dict(m=8),
        "sample_seed": dict(method="noise_tunnel", sample_seed=1),
        "baseline_pad_id": dict(pad_id=1),
        "attn": dict(attn="flash"),
        "fused": dict(fused=True),
        "adaptive": dict(adaptive=True, tol=1e-2),
    }
    keys = {"base": base}
    for name, kw in variants.items():
        keys[name] = _engine(cfg, params, **kw).request_cache_key(req)
    # a different sample seed only matters to ensemble methods — compare it
    # against the same method at the default seed, not against base
    keys["sample_seed_ref"] = _engine(
        cfg, params, method="noise_tunnel"
    ).request_cache_key(req)
    assert keys["sample_seed"] != keys["sample_seed_ref"]
    del keys["sample_seed"], keys["sample_seed_ref"]
    vals = list(keys.values())
    assert len(set(vals)) == len(vals), (
        f"key collision across knobs: {keys}"
    )


def test_key_covers_model_fingerprint_and_mesh(lm):
    cfg, model, params = lm
    req = _req(cfg)
    base = _engine(cfg, params).request_cache_key(req)
    other_params = model.init(jax.random.PRNGKey(1))
    assert _engine(cfg, other_params).request_cache_key(req) != base, (
        "different weights must never share attribution entries"
    )
    eng = _engine(cfg, params)
    eng._mesh_key = ("data", 2, "model", 1)  # what a dp=2 mesh records
    assert eng.request_cache_key(req) != base


def test_key_ignores_batch_composition(lm):
    """Padding invariance: the key is per-request — co-batched traffic and
    the bucket a request lands in do NOT change it (so a request cached
    from a full batch hits when it arrives alone)."""
    cfg, _, params = lm
    eng = _engine(cfg, params, result_cache=1 << 20)
    reqs = [_req(cfg, n=7), _req(cfg, n=12, seed=2), _req(cfg, n=7, seed=3)]
    batched = eng.explain(reqs)
    solo = eng.explain([reqs[0]])[0]
    assert eng.stats.result_hits >= 1, "solo replay must hit the batched entry"
    np.testing.assert_array_equal(
        solo["token_scores"], batched[0]["token_scores"]
    )


# ------------------------------------------------------------ (b) replay


def test_hit_is_bit_identical_and_tamper_proof(lm):
    cfg, _, params = lm
    eng = _engine(cfg, params, result_cache=1 << 20)
    ref = _engine(cfg, params)
    reqs = [_req(cfg), _req(cfg, n=12, seed=2)]
    first = eng.explain(reqs)
    fresh = ref.explain(reqs)
    hit = eng.explain(reqs)
    assert eng.stats.result_hits == len(reqs)
    for a, b, c in zip(first, hit, fresh):
        np.testing.assert_array_equal(a["token_scores"], b["token_scores"])
        np.testing.assert_array_equal(b["token_scores"], c["token_scores"])
        assert a["delta"] == b["delta"] == c["delta"]
    # caller mutation of a returned hit never reaches the stored bytes
    hit[0]["token_scores"][:] = -1.0
    again = eng.explain([reqs[0]])[0]
    np.testing.assert_array_equal(again["token_scores"], first[0]["token_scores"])


def test_raw_rows_served_from_cache(lm):
    """Entries are stored WITH the raw bucket row, so a hit can serve both
    ``return_raw`` variants regardless of which variant populated it."""
    cfg, _, params = lm
    eng = _engine(cfg, params, result_cache=1 << 20)
    req = _req(cfg)
    plain = eng.explain([req])[0]
    assert "raw_token_scores" not in plain
    raw = eng.explain([req], return_raw=True)[0]
    assert eng.stats.result_hits == 1
    assert raw["raw_token_scores"].shape == (8,)  # padded bucket row


# ------------------------------------------------------------ (c) eviction


def test_lru_eviction_respects_byte_budget():
    entry = {"token_scores": np.ones(64, np.float32)}
    size = _entry_bytes(entry)
    rc = ResultCache(max_bytes=3 * size)
    for i in range(5):
        rc.put(f"k{i}", entry)
        assert rc.bytes <= rc.max_bytes, "budget must hold after EVERY put"
    assert len(rc) == 3 and rc.evictions == 2
    assert rc.get("k0") is None and rc.get("k1") is None  # oldest evicted
    assert rc.get("k4") is not None
    # recency: touching k2 makes k3 the next victim
    rc.get("k2")
    rc.put("k5", entry)
    assert "k3" not in rc and "k2" in rc


def test_oversize_entry_refused():
    rc = ResultCache(max_bytes=128)
    rc.put("big", {"token_scores": np.ones(1024, np.float32)})
    assert len(rc) == 0 and rc.evictions == 1 and rc.bytes == 0


def test_repeat_put_replaces_not_duplicates():
    rc = ResultCache(max_bytes=1 << 20)
    e = {"token_scores": np.ones(8, np.float32)}
    rc.put("k", e)
    b1 = rc.bytes
    rc.put("k", e)
    assert len(rc) == 1 and rc.bytes == b1


# ------------------------------------------------------- (d) warm start


@pytest.fixture(scope="module")
def warmed(lm):
    """One served engine + its saved warm state (module-scoped: compiles)."""
    cfg, _, params = lm
    import tempfile

    eng = _engine(cfg, params, result_cache=1 << 20)
    reqs = [_req(cfg), _req(cfg, n=12, seed=2)]
    out = eng.explain(reqs)
    td = tempfile.mkdtemp()
    save_warm_state(eng, td)
    return cfg, params, eng, reqs, out, td


def test_warm_restore_zero_compiles_and_bit_identical(lm, warmed):
    cfg, params, _, reqs, out, td = warmed
    eng2 = _engine(cfg, params, result_cache=1 << 20)
    rep = load_warm_state(eng2, td)
    assert rep.restored and rep.executables > 0
    if jax.default_backend() == "cpu":
        # XLA:CPU refuses to serialize these executables natively; the
        # portable jax.export form must carry the whole set
        assert rep.via == "export"
    replay = eng2.explain(reqs)
    assert eng2.stats.compiles == 0, "restored engine must never compile"
    assert eng2.stats.prep_compiles == 0, "restored engine must never compile"
    for a, b in zip(out, replay):
        np.testing.assert_array_equal(a["token_scores"], b["token_scores"])
        assert a["delta"] == b["delta"]


def test_warm_restore_corrupted_shard_falls_back_cold(lm, warmed, tmp_path):
    import os
    import shutil

    cfg, params, _, reqs, _, td = warmed
    broken = str(tmp_path / "warm")
    shutil.copytree(td, broken)
    with open(os.path.join(broken, "executables.pkl"), "r+b") as fh:
        fh.seek(0)
        fh.write(b"\x00" * 16)
    eng2 = _engine(cfg, params)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rep = load_warm_state(eng2, broken)
    assert not rep.restored and "corrupted" in rep.reason
    assert any("cold" in str(x.message) for x in w)
    # correctness is unaffected: the cold engine still serves (and compiles)
    out = eng2.explain([reqs[0]])
    assert eng2.stats.compiles > 0 and np.isfinite(out[0]["delta"])


def test_warm_restore_context_mismatch_falls_back_cold(lm, warmed):
    cfg, params, _, _, _, td = warmed
    eng2 = _engine(cfg, params, m=8)  # different m -> different context
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rep = load_warm_state(eng2, td)
    assert not rep.restored and "context" in rep.reason
    assert eng2._cache == {} and eng2._prep_cache == {}


def test_warm_restore_save_cycle_preserves_executables(lm, warmed, tmp_path):
    """restore -> save must carry the restored executables forward: they have
    no export info (their builder fns never ran) and cannot be re-serialized,
    so the cycle reuses the original blobs instead of shrinking the state."""
    import json
    import os

    cfg, params, _, reqs, out, td = warmed
    eng2 = _engine(cfg, params, result_cache=1 << 20)
    assert load_warm_state(eng2, td).restored
    resaved = str(tmp_path / "warm2")
    save_warm_state(eng2, resaved)
    with open(os.path.join(resaved, "manifest.json")) as fh:
        n = json.load(fh)["n_executables"]
    # executables and bucket prep programs alike
    assert eng2._prep_cache
    assert n == len(eng2._cache) + len(eng2._prep_cache) > 0, (
        "restore->save shrank the warm state"
    )
    eng3 = _engine(cfg, params, result_cache=1 << 20)
    rep = load_warm_state(eng3, resaved)
    assert rep.restored and rep.executables == n
    replay = eng3.explain(reqs)
    assert eng3.stats.compiles == 0 and eng3.stats.prep_compiles == 0
    for a, b in zip(out, replay):
        np.testing.assert_array_equal(a["token_scores"], b["token_scores"])


def test_warm_restore_missing_dir_is_quiet_cold(lm, tmp_path):
    cfg, _, params = lm
    eng = _engine(cfg, params)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rep = load_warm_state(eng, str(tmp_path / "nope"))
    assert not rep.restored and rep.reason == "no warm state"
    assert not w, "a first boot has no warm state — that is not a warning"


# -------------------------------------------------- (e) scheduler admission


def test_scheduler_cached_explain_completes_at_admission(lm):
    from repro.runtime.fault import FaultConfig
    from repro.serve import MixedScheduler

    cfg, _, params = lm
    eng = _engine(cfg, params, result_cache=1 << 20)
    sched = MixedScheduler(
        eng, max_len=16, decode_chunk=2,
        fault_cfg=FaultConfig(max_retries=1, backoff_base_s=0.0),
    )
    req = _req(cfg)
    t1 = sched.submit(req)
    sched.run_until_idle()
    assert t1.status == "done"
    t2 = sched.submit(req)
    assert t2.status == "done", "a cached request completes AT admission"
    assert sched.queue_depth == 0, "hits never occupy a queue slot"
    np.testing.assert_array_equal(
        t1.result["token_scores"], t2.result["token_scores"]
    )
    assert "raw_token_scores" not in t2.result


def test_degraded_results_never_cached(lm):
    from repro.runtime.fault import FaultConfig
    from repro.serve import MixedScheduler

    cfg, _, params = lm
    eng = _engine(cfg, params, result_cache=1 << 20)
    sched = MixedScheduler(
        eng, max_len=16, decode_chunk=2,
        fault_cfg=FaultConfig(max_retries=1, backoff_base_s=0.0),
    )

    def poison(kind, payload):
        if kind.startswith("exp"):
            raise RuntimeError("injected")

    sched.fault_hook = poison
    req = _req(cfg, seed=9)
    t1 = sched.submit(req)
    sched.run_until_idle()
    assert t1.status == "degraded"
    sched.fault_hook = None
    t2 = sched.submit(req)
    sched.run_until_idle()
    assert t2.status == "done" and not t2.result["degraded"], (
        "the fault-path zero vector must not be replayed from the cache"
    )
