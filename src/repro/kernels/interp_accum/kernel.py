"""Fused interpolate-into-VJP kernels for the bandwidth-optimal stage 2.

The fused stage 2 (``ig.attribute(fused=True)``, DESIGN.md §10) differentiates
``carry ↦ f(interp(x, x′, α) + carry)`` at ``carry = 0``. Its two halves map
onto two single-pass kernels:

  * forward — ``interp_add_pallas``: one pass generating the interpolant tile
    b + α(x − b) + carry in VMEM, reading each (x, x′) feature tile once per
    K-tile (the ``kernels.interpolate`` amortization) AND folding the additive
    carry in, so the fused chunk function costs no extra HBM round trip over
    plain interpolation. The carry is either (B, F) f32 — the riemann-class
    broadcast over the step axis — or (B, K, F) f32 — the per-step probe the
    quadratic (IDGI) class differentiates against.
  * backward — ``accum_cot_pallas``: the transpose of the broadcast-add IS
    the weighted accumulation (the quadrature weights ride the VJP seed).
    One pass over the cotangent ḡ with the riemann carry structure: grid
    (B, F/Ft, K/Kt), K innermost so the (1, Ft) f32 output tile stays
    resident in VMEM across the whole step axis — 1 output write per F-tile
    instead of K read-modify-write round trips. The per-step (B, K, F)
    carry's transpose is an identity (plus the f32 cast) — the quadratic
    (IDGI) class pays no kernel at all on the way back.

Block layout as in ``kernels.ig_accum``: batch dim squeezed, (B, F) operands
as (B, 1, F) with (1, Ft) tiles, (B, K) operands as (B, K, 1) with (Kt, 1)
tiles — the TPU tiling rule for a block's last two dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interp(x_ref, b_ref, a_ref):
    # interpolation at INPUT precision — bit-compatible with the unfused
    # path's quadrature nodes (paths.interp_add dtype contract, §10) — then
    # lifted to f32 for the carry add
    x = x_ref[...]  # (1, Ft) input dtype
    b = b_ref[...]  # (1, Ft)
    a = a_ref[...].astype(x.dtype)  # (Kt, 1)
    return (b + a * (x - b)).astype(jnp.float32)  # (Kt, Ft)


def _interp_add_kernel(x_ref, b_ref, a_ref, u_ref, o_ref):
    # u: (1, Ft) f32 broadcast over steps, or (Kt, Ft) f32 per-step carry
    o_ref[...] = (_interp(x_ref, b_ref, a_ref) + u_ref[...]).astype(o_ref.dtype)


def _accum_cot_kernel(g_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum(g_ref[...].astype(jnp.float32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def interp_add_pallas(
    x: jax.Array,
    baseline: jax.Array,
    alphas: jax.Array,
    carry: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """x, baseline: (B, F); alphas: (B, K); carry: (B, F) or (B, K, F) f32
    -> (B, K, F) in x.dtype: b + α(x − b) + carry, one fused pass."""
    B, F = x.shape
    K = alphas.shape[1]
    bk, bf = min(block_k, K), min(block_f, F)
    assert K % bk == 0 and F % bf == 0, (K, bk, F, bf)
    row = pl.BlockSpec((None, 1, bf), lambda b, k, f: (b, 0, f))
    tile = pl.BlockSpec((None, bk, bf), lambda b, k, f: (b, k, f))
    bcast = carry.ndim == 2
    return pl.pallas_call(
        _interp_add_kernel,
        grid=(B, K // bk, F // bf),
        in_specs=[
            row,
            row,
            pl.BlockSpec((None, bk, 1), lambda b, k, f: (b, k, 0)),
            row if bcast else tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, K, F), x.dtype),
        interpret=interpret,
    )(x[:, None, :], baseline[:, None, :], alphas[:, :, None],
      carry[:, None, :] if bcast else carry)


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def accum_cot_pallas(
    grads: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """grads (B, K, F) -> (B, F) f32 = Σ_k grads[:, k] — the fused backward.

    The weighted accumulation of the fused stage 2: the quadrature weights
    already ride the cotangent (they seed the VJP at the model output), so
    the transpose of the step-axis broadcast is a plain K-reduction with the
    f32 output tile carried in VMEM (K innermost)."""
    B, K, F = grads.shape
    bk, bf = min(block_k, K), min(block_f, F)
    assert K % bk == 0 and F % bf == 0, (K, bk, F, bf)
    row = pl.BlockSpec((None, 1, bf), lambda b, f, k: (b, 0, f))
    out = pl.pallas_call(
        _accum_cot_kernel,
        grid=(B, F // bf, K // bk),
        in_specs=[pl.BlockSpec((None, bk, bf), lambda b, f, k: (b, k, f))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, F), jnp.float32),
        interpret=interpret,
    )(grads)
    return out[:, 0, :]
