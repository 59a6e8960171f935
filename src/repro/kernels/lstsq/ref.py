"""Pure-jnp oracles for the batched weighted-least-squares solve (LIME).

The serving path accumulates the weighted normal equations
``A = XᵀWX`` / ``b = XᵀWy`` chunk-wise (``core.perturb.lime_update``) and
solves ``(A + λI) β = b`` per batch row. ``prepare_normal_eqs`` is the ONE
shared pre-solve step — ridge regularization plus mask-aware pinning for
ragged batches — used by both this oracle and the Pallas op, so kernel
parity is over the solve itself.

Mask pinning: rows/columns of invalid entries (e.g. LIME groups with no
real position in a padded bucket) are zeroed and their diagonal set to 1
with a zero right-hand side, so their solution entry is EXACTLY zero and
they are fully decoupled from the valid block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def prepare_normal_eqs(
    A: jax.Array,
    rhs: jax.Array,
    mask: Optional[jax.Array] = None,
    ridge: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """(…, N, N), (…, N) → the regularized, mask-pinned system (f32 minimum).

    bf16 inputs are upcast to f32 (the class's accumulation dtype); f64
    rides through under ``jax.enable_x64``.
    """
    dt = jnp.promote_types(A.dtype, jnp.float32)
    A = A.astype(dt)
    rhs = rhs.astype(dt)
    N = A.shape[-1]
    eye = jnp.eye(N, dtype=dt)
    A = A + jnp.asarray(ridge, dt) * eye
    if mask is not None:
        m = mask.astype(dt)
        A = A * (m[..., :, None] * m[..., None, :]) + (1.0 - m)[..., :, None] * eye
        rhs = rhs * m
    return A, rhs


def wls_solve_ref(
    A: jax.Array,
    rhs: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    ridge: float = 0.0,
) -> jax.Array:
    """Batched solve of the (regularized, pinned) normal equations.

    A: (B, N, N); rhs: (B, N); mask: optional (B, N) valid-entry mask
    -> (B, N) in the promoted (≥ f32) dtype. The oracle for
    ``kernels.lstsq.ops.wls_solve`` and the default LIME solve hook.
    """
    Ap, bp = prepare_normal_eqs(A, rhs, mask, ridge)
    return jnp.linalg.solve(Ap, bp[..., None])[..., 0]


def normal_eqs(
    X: jax.Array, w: jax.Array, y: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Assemble (XᵀWX, XᵀWy) from a raw weighted design — the unchunked
    form of ``core.perturb.lime_update``'s accumulation (test/bench helper).

    X: (…, P, N) design rows; w: (…, P) weights; y: (…, P) responses.
    """
    Xw = X * w[..., None]
    return (
        jnp.einsum("...pi,...pj->...ij", Xw, X),
        jnp.einsum("...pi,...p->...i", Xw, y),
    )
