"""Per-kernel shape/dtype sweeps against the pure-jnp ref oracles.

All kernels run in interpret=True (Pallas kernel body executed in Python on
CPU) — the BlockSpec tiling/grid logic is exactly what a TPU would execute.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ig, schedule
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ig_accum.ops import accum_fn_for, ig_accum, ig_accum_idgi
from repro.kernels.ig_accum.ref import ig_accum_idgi_ref, ig_accum_ref
from repro.kernels.interpolate.ops import interpolate as interpolate_k
from repro.kernels.interpolate.ref import interpolate_ref
from repro.kernels.lstsq import ref as lstsq_ref
from repro.kernels.lstsq.ops import prepare_normal_eqs, wls_solve
from repro.kernels.lstsq.ref import wls_solve_ref

KEY = jax.random.PRNGKey(0)

# Parity must hold on UNFRIENDLY shapes — odd, prime, non-pow2 K and F that
# exercise the pad-to-block paths — and under the numerics the deploy targets
# actually use: f32, bf16 (TPU compute dtype), and f64 (x64-enabled hosts).
ODD_SHAPES = [(1, 3, 17), (2, 7, 33), (3, 5, 130), (2, 9, 257)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float64]


def _dtype_ctx(dtype):
    """x64 must be enabled around f64 parity cases (and only those)."""
    if dtype == jnp.float64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _tol(dtype):
    return {jnp.float32: 1e-5, jnp.float64: 1e-5, jnp.bfloat16: 3e-2}[dtype]


def _ragged_mask(B, F):
    """Ragged real-position mask: row b keeps a different odd prefix."""
    lens = [max(1, (F * (b + 1)) // (B + 1) - b) for b in range(B)]
    m = np.zeros((B, F), np.float32)
    for b, n in enumerate(lens):
        m[b, :n] = 1.0
    return jnp.asarray(m)


# ------------------------------------------------------------- interpolate


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,K,F", [(1, 1, 8), (2, 7, 300), (3, 8, 512), (2, 16, 1024), (1, 5, 33)]
)
def test_interpolate_matches_ref(B, K, F, dtype):
    x = jax.random.normal(KEY, (B, F)).astype(dtype)
    b = (0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (B, F))).astype(dtype)
    a = jax.random.uniform(jax.random.fold_in(KEY, 2), (B, K))
    got = interpolate_k(x, b, a)
    want = interpolate_ref(x, b, a)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_interpolate_nd_features():
    """Engine adapter flattens arbitrary feature shapes."""
    x = jax.random.normal(KEY, (2, 3, 5, 7))
    b = jnp.zeros_like(x)
    a = jax.random.uniform(KEY, (4,))
    got = interpolate_k(x, b, a)
    assert got.shape == (2, 4, 3, 5, 7)
    from repro.core.paths import interpolate as engine_ref

    np.testing.assert_allclose(
        np.asarray(got), np.asarray(engine_ref(x, b, a)), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------- ig_accum


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,K,F", [(1, 1, 8), (2, 7, 300), (4, 8, 512), (2, 9, 1000)])
def test_ig_accum_matches_ref(B, K, F, dtype):
    g = jax.random.normal(KEY, (B, K, F)).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(KEY, 1), (B, K))
    acc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, F))
    got = ig_accum(acc, g, w)
    want = ig_accum_ref(acc, g, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_kernels_inside_engine():
    """Pallas kernels injected into the IG engine reproduce the jnp path."""

    def f(xs, t):
        return jnp.sum(xs**2, axis=-1)

    x = jax.random.normal(KEY, (2, 64))
    bl = jnp.zeros_like(x)
    t = jnp.zeros((2,), jnp.int32)
    sched = schedule.uniform(8)
    base = ig.attribute(f, x, bl, sched, t)

    # the ops wrappers honor the MethodSpec accumulator signature directly
    fused = ig.attribute(
        f, x, bl, sched, t, interp_fn=interpolate_k, accum_fn=ig_accum
    )
    np.testing.assert_allclose(
        np.asarray(base.attributions), np.asarray(fused.attributions), rtol=1e-4, atol=1e-5
    )


# ------------------------------------- odd shapes × masks × {f32, bf16, f64}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,K,F", ODD_SHAPES)
def test_interpolate_odd_shapes_masked(B, K, F, dtype):
    with _dtype_ctx(dtype):
        x = jax.random.normal(KEY, (B, F)).astype(dtype)
        b = (0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (B, F))).astype(dtype)
        a = jax.random.uniform(jax.random.fold_in(KEY, 2), (B, K))
        mask = _ragged_mask(B, F)
        got = interpolate_k(x, b, a, mask=mask)
        pinned = jnp.where(mask.astype(bool), x, b)
        want = interpolate_ref(pinned, b, a)
        tol = _tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )
        # masked positions sit EXACTLY at the baseline for every alpha
        off = np.asarray(mask) == 0.0
        np.testing.assert_array_equal(
            np.asarray(got, np.float32)[:, :, :][np.broadcast_to(off[:, None, :], got.shape)],
            np.broadcast_to(np.asarray(b, np.float32)[:, None, :], got.shape)[
                np.broadcast_to(off[:, None, :], got.shape)
            ],
        )


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,K,F", ODD_SHAPES)
def test_ig_accum_odd_shapes_masked(B, K, F, dtype):
    with _dtype_ctx(dtype):
        g = jax.random.normal(KEY, (B, K, F)).astype(dtype)
        w = jax.random.uniform(jax.random.fold_in(KEY, 1), (B, K))
        acc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, F)).astype(jnp.float32)
        mask = _ragged_mask(B, F)
        got = ig_accum(acc, g, w, mask=mask)
        want = ig_accum_ref(acc, g * mask[:, None, :].astype(g.dtype), w)
        tol = _tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=tol, atol=tol
        )


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,K,F", ODD_SHAPES)
def test_ig_accum_idgi_odd_shapes_masked(B, K, F, dtype):
    """The IDGI weighting pass: two-pass Pallas vs the einsum oracle, on
    pad-exercising shapes, with ragged masks, under each deploy dtype."""
    with _dtype_ctx(dtype):
        g = jax.random.normal(KEY, (B, K, F)).astype(dtype)
        w = jax.random.uniform(jax.random.fold_in(KEY, 1), (B, K))
        acc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, F)).astype(jnp.float32)
        d = jax.random.normal(jax.random.fold_in(KEY, 3), (B, F)).astype(dtype)
        mask = _ragged_mask(B, F)
        mg = mask[:, None, :].astype(g.dtype)
        got = ig_accum_idgi(acc, g, w, diff=d, mask=mask)
        want = ig_accum_idgi_ref(acc, g * mg, w, d)
        tol = _tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=tol, atol=tol
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ig_accum_idgi_friendly_shapes(dtype):
    B, K, F = 2, 8, 512  # no padding: the pure-kernel path
    g = jax.random.normal(KEY, (B, K, F)).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(KEY, 1), (B, K))
    acc = jnp.zeros((B, F), jnp.float32)
    d = jax.random.normal(jax.random.fold_in(KEY, 3), (B, F)).astype(dtype)
    got = ig_accum_idgi(acc, g, w, diff=d)
    want = ig_accum_idgi_ref(acc, g, w, d)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_ig_accum_idgi_zero_gradient_rows():
    """⟨g, g⟩ == 0 steps contribute exactly zero, never NaN."""
    g = jnp.zeros((1, 4, 16))
    out = ig_accum_idgi(
        jnp.zeros((1, 16)), g, jnp.ones((1, 4)), diff=jnp.ones((1, 16))
    )
    assert bool(jnp.isfinite(out).all()) and float(jnp.abs(out).sum()) == 0.0


def test_idgi_kernel_inside_engine():
    """Pallas IDGI kernels injected into the IG engine == the jnp method."""

    def f(xs, t):
        return jnp.tanh((xs**2).sum(-1) / 10.0)

    x = jax.random.normal(KEY, (2, 64)) + 1.0
    bl = jnp.zeros_like(x)
    t = jnp.zeros((2,), jnp.int32)
    sched = schedule.uniform(8)
    base = ig.attribute(f, x, bl, sched, t, method="idgi")
    fused = ig.attribute(
        f, x, bl, sched, t, method="idgi",
        interp_fn=interpolate_k, accum_fn=accum_fn_for("idgi"),
    )
    np.testing.assert_allclose(
        np.asarray(base.attributions), np.asarray(fused.attributions),
        rtol=1e-4, atol=1e-6,
    )
    with pytest.raises(ValueError, match="riemann"):
        accum_fn_for("simpson")


# --------------------------------------------------------- flash attention


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,S,NQ,NKV,D", [(1, 128, 4, 4, 64), (1, 256, 4, 2, 64), (2, 128, 8, 2, 32)]
)
def test_flash_attention_matches_ref(B, S, NQ, NKV, D, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, NQ, S, D))
    k = jax.random.normal(ks[1], (B, NKV, S, D))
    v = jax.random.normal(ks[2], (B, NKV, S, D))
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-3)


def test_flash_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


def test_flash_wrapper_model_layout():
    """(B, S, H, D) wrapper output matches blocked_attention used in models."""
    from repro.models.attention import blocked_attention

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = blocked_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-3)


# ------------------------------------------------- lstsq (LIME WLS solve)


def _wls_system(B, P, N, dtype, *, seed=0, dup_cols=0):
    """A well-posed weighted design and its normal equations (B, N, N)/(B, N).

    ``dup_cols`` > 0 duplicates trailing design columns — an exactly
    rank-deficient XᵀWX that only the ridge makes solvable."""
    k = jax.random.fold_in(KEY, seed)
    X = jax.random.normal(k, (B, P, N))
    if dup_cols:
        X = X.at[..., -dup_cols:].set(X[..., :dup_cols])
    w = jax.random.uniform(jax.random.fold_in(k, 1), (B, P), minval=0.1)
    y = jax.random.normal(jax.random.fold_in(k, 2), (B, P))
    A, rhs = lstsq_ref.normal_eqs(X, w, y)
    return A.astype(dtype), rhs.astype(dtype)


def _lstsq_tol(dtype):
    # the solve amplifies input error by the (ridge-bounded) condition
    # number, so the bands are wider than the elementwise kernels'
    return {jnp.float32: 1e-3, jnp.float64: 1e-8, jnp.bfloat16: 1e-3}[dtype]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,P,N", [(1, 9, 3), (2, 21, 7), (3, 40, 17), (2, 50, 22)])
def test_wls_solve_matches_ref_and_lstsq(B, P, N, dtype):
    """Pallas Gauss–Jordan vs the jnp oracle vs ``jnp.linalg.lstsq`` on the
    SAME prepared (ridge-regularized) system — odd / non-pow2 N exercises
    the identity-row padding to the sublane multiple."""
    with _dtype_ctx(dtype):
        A, rhs = _wls_system(B, P, N, dtype)
        ridge = 0.1
        got = wls_solve(A, rhs, ridge=ridge, interpret=True)
        want = wls_solve_ref(A, rhs, ridge=ridge)
        tol = _lstsq_tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=tol, atol=tol,
        )
        Ap, bp = prepare_normal_eqs(A, rhs, ridge=ridge)
        direct = jnp.stack(
            [jnp.linalg.lstsq(Ap[b], bp[b])[0] for b in range(B)]
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(direct, np.float64),
            rtol=10 * tol, atol=10 * tol,
        )


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,P,N", [(2, 21, 7), (3, 40, 17)])
def test_wls_solve_ragged_mask(B, P, N, dtype):
    """Masked (ragged-batch) entries are pinned: β EXACTLY zero there, and
    the valid block solves the same system the oracle solves."""
    with _dtype_ctx(dtype):
        A, rhs = _wls_system(B, P, N, dtype, seed=3)
        mask = _ragged_mask(B, N)
        got = wls_solve(A, rhs, mask=mask, ridge=0.1, interpret=True)
        want = wls_solve_ref(A, rhs, mask=mask, ridge=0.1)
        assert np.all(np.asarray(got)[np.asarray(mask) == 0.0] == 0.0)
        tol = _lstsq_tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=tol, atol=tol,
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_wls_solve_rank_deficient_regularized(dtype, B=2, P=24, N=8):
    """Duplicated design columns make XᵀWX exactly singular; the ridge is
    what makes the system solvable, and the no-pivot sweep must still agree
    with the oracle AND actually satisfy the regularized equations."""
    with _dtype_ctx(dtype):
        A, rhs = _wls_system(B, P, N, dtype, seed=7, dup_cols=2)
        ridge = 0.5
        got = wls_solve(A, rhs, ridge=ridge, interpret=True)
        want = wls_solve_ref(A, rhs, ridge=ridge)
        tol = _lstsq_tol(dtype)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=tol, atol=tol,
        )
        Ap, bp = prepare_normal_eqs(A, rhs, ridge=ridge)
        resid = jnp.einsum("bij,bj->bi", Ap, got) - bp
        assert float(jnp.abs(resid).max()) < 10 * tol * float(jnp.abs(bp).max() + 1.0)


def test_wls_solve_inside_lime():
    """The kernel drops into the LIME solve hook and reproduces the oracle
    end-to-end (the engine's use_kernels injection point)."""
    from repro.core import perturb

    def f(xs, t):
        return jnp.sum(jnp.tanh(xs), axis=(1, 2))

    x = jax.random.normal(KEY, (2, 12, 3)) + 1.0
    bl = jnp.zeros_like(x)
    t = jnp.zeros((2,), jnp.int32)
    base = perturb.PerturbExplainer(f, method="lime", n_masks=16)
    kern = perturb.PerturbExplainer(
        f, method="lime", n_masks=16,
        solve_fn=lambda A, rhs, **kw: wls_solve(A, rhs, interpret=True, **kw),
    )
    a = np.asarray(base.attribute(x, bl, t).attributions)
    b = np.asarray(kern.attribute(x, bl, t).attributions)
    # elimination order differs from LU under the small default ridge
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
