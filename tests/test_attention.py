"""Flash-attention custom-VJP parity + attention-model serving contracts.

Kernel legs mirror tests/test_kernels.py: interpret-mode Pallas vs the
pure-jnp ref oracles on pad-exercising odd shapes, ragged kv lengths, and
GQA head maps, under the deploy numerics (f32, bf16; f64 opts in per-test
via jax.enable_x64). Engine legs pin the serving contracts the
attention-parity CI job gates: fused and unfused adaptive escalation traces
are EXACTLY equal on a flash LM, and a ViT engine serves patch-feature
requests with zero steady-state recompiles.
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import (
    attention_ref,
    attention_vjp_ref,
    flash_attention,
)

KEY = jax.random.PRNGKey(0)

# (B, S, HQ, HKV, D): odd/prime S exercises the pad-to-block path, HQ != HKV
# exercises the GQA head map in both backward kernels.
SHAPES = [(1, 17, 4, 2, 8), (2, 33, 6, 6, 4)]


def _dtype_ctx(dtype):
    """x64 must be enabled around f64 parity cases (and only those)."""
    if dtype == jnp.float64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _tol(dtype):
    return {jnp.float32: 1e-4, jnp.float64: 1e-4, jnp.bfloat16: 3e-2}[dtype]


def _qkv(B, S, HQ, HKV, D, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, HQ, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, HKV, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, HKV, D)).astype(dtype)
    return q, k, v


def _lengths(B, S, ragged):
    """Ragged kv lengths: every row keeps a different non-pow2 prefix."""
    if not ragged:
        return None
    return jnp.asarray(
        [max(1, (S * (b + 1)) // (B + 1)) for b in range(B)], jnp.int32
    )


def _t(x):
    return x.transpose(0, 2, 1, 3)  # model (B,S,H,D) <-> kernel (B,H,S,D)


def _ref_model_layout(q, k, v, *, causal, lengths):
    return _t(attention_ref(_t(q), _t(k), _t(v), causal=causal, lengths=lengths))


# --------------------------------------------------------------- forward


@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,HQ,HKV,D", SHAPES)
def test_flash_forward_parity(B, S, HQ, HKV, D, causal, ragged):
    q, k, v = _qkv(B, S, HQ, HKV, D)
    lens = _lengths(B, S, ragged)
    got = flash_attention(q, k, v, causal=causal, lengths=lens, block_q=8, block_k=8)
    want = _ref_model_layout(q, k, v, causal=causal, lengths=lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# -------------------------------------------------------------- backward


def _grads(fn, q, k, v, do):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * do)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,HQ,HKV,D", SHAPES)
def test_flash_vjp_parity(B, S, HQ, HKV, D, causal, ragged):
    q, k, v = _qkv(B, S, HQ, HKV, D)
    lens = _lengths(B, S, ragged)
    do = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, HQ, D))

    got = _grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, lengths=lens, block_q=8, block_k=8
        ),
        q, k, v, do,
    )
    want = _grads(
        lambda q, k, v: _ref_model_layout(q, k, v, causal=causal, lengths=lens),
        q, k, v, do,
    )
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} mismatch vs jax.grad(ref)",
        )
    # and against the explicit analytic VJP oracle (kernel layout)
    dq2, dk2, dv2 = attention_vjp_ref(
        _t(q), _t(k), _t(v), _t(do), causal=causal, lengths=lens
    )
    for g, w, name in zip(got, (_t(dq2), _t(dk2), _t(dv2)), "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} mismatch vs attention_vjp_ref",
        )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float64])
def test_flash_fwd_bwd_parity_dtypes(dtype):
    """bf16 (TPU compute dtype) and f64 (x64 hosts) on one GQA ragged case."""
    B, S, HQ, HKV, D = 2, 33, 4, 2, 8
    with _dtype_ctx(dtype):
        q, k, v = _qkv(B, S, HQ, HKV, D, dtype)
        lens = _lengths(B, S, True)
        tol = _tol(dtype)
        got = flash_attention(q, k, v, causal=True, lengths=lens, block_q=8, block_k=8)
        want = _ref_model_layout(q, k, v, causal=True, lengths=lens)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )
        do = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, HQ, D))
        got_g = _grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, lengths=lens, block_q=8, block_k=8
            ),
            q, k, v, do,
        )
        want_g = _grads(
            lambda q, k, v: _ref_model_layout(q, k, v, causal=True, lengths=lens),
            q, k, v, do,
        )
        for g, w, name in zip(got_g, want_g, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=tol, atol=tol, err_msg=f"d{name} mismatch under {dtype}",
            )


# ------------------------------------------------------- engine contracts


def test_engine_flash_traces_fused_equals_unfused():
    """δ-adaptive escalation on a flash LM is program-structure identical
    fused vs unfused: per-request (m_used, hops, converged) match exactly."""
    from repro.configs import ARCHS, reduced
    from repro.launch.explain import make_traffic
    from repro.models.registry import model_for
    from repro.serve import ExplainEngine

    cfg = dataclasses.replace(reduced(ARCHS["llama3-8b"]), compute_dtype="float32")
    params = model_for(cfg).init(jax.random.PRNGKey(0))
    reqs = make_traffic(cfg, 4, 5, 14, np.random.default_rng(0))
    traces = {}
    for fused in (False, True):
        eng = ExplainEngine(
            cfg, params, m=4, n_int=2, adaptive=True, tol=1e-2, m_max=16,
            fused=fused, attn="flash", seq_buckets=(8, 16),
        )
        res = eng.explain(reqs)
        traces[fused] = [(r["m_used"], r["hops"], r["converged"]) for r in res]
    assert traces[True] == traces[False]


def test_vit_engine_serves_patch_features():
    """Feature-space requests: per-patch scores, finite δ, and replaying the
    same traffic hits the warmed executable cache (zero recompiles)."""
    from repro.configs.vit import reduced_vit
    from repro.models import vit
    from repro.serve import ExplainEngine, ExplainRequest

    cfg = reduced_vit()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    imgs = jax.random.uniform(
        jax.random.PRNGKey(1), (3, cfg.image_size, cfg.image_size, cfg.channels)
    )
    feats = np.asarray(vit.patchify(cfg, imgs), np.float32)
    reqs = [
        ExplainRequest(
            tokens=np.arange(cfg.num_patches, dtype=np.int32),
            target=int(i % cfg.num_classes),
            features=f,
        )
        for i, f in enumerate(feats)
    ]
    eng = ExplainEngine(
        cfg, params, m=4, n_int=2, fused=True, attn="flash",
        seq_buckets=(cfg.num_patches,),
    )
    res = eng.explain(reqs)
    assert len(res) == len(reqs)
    assert all(len(r["token_scores"]) == cfg.num_patches for r in res)
    assert all(np.isfinite(r["delta"]) for r in res)
    misses = eng.stats.misses
    eng.explain(reqs)
    assert eng.stats.misses == misses


def test_mixed_feature_token_traffic_rejected():
    from repro.serve import ExplainRequest
    from repro.serve.batching import plan_buckets

    reqs = [
        ExplainRequest(
            tokens=np.arange(8, dtype=np.int32), target=0,
            features=np.ones((8, 4), np.float32),
        ),
        ExplainRequest(tokens=np.arange(8, dtype=np.int32), target=0),
    ]
    with pytest.raises(ValueError, match="mixed"):
        plan_buckets(reqs)
