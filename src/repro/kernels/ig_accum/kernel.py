"""Fused accumulation kernels for the stage-2 hot loop.

Riemann: acc += Σ_k w_k · g_k. The non-uniform interval widths ride in w —
stage 2 of the paper is exactly this reduction. Fusing keeps the running
attribution tile resident in VMEM across the K (steps) grid dimension instead
of K× read-modify-write round trips to HBM (memory-bound op: 1 output write
per K-tile instead of K).

Grid: (B, F/Ft, K/Kt) — K is the innermost (sequential) dimension so the
output tile is revisited with carry semantics; f32 accumulation.

Block layout (TPU tiling rule: a block's last two dims are multiples of
(8, 128) or the full array dims): the batch dim is squeezed, (B, F) operands
ride as (B, 1, F) with (1, Ft) tiles and (B, K) operands as (B, K, 1) with
(Kt, 1) tiles, so every kernel body sees 2-D refs.

IDGI (DESIGN.md §8) adds the gradient-direction weighting
``acc += Σ_k c_k g_k²`` with ``c_k = w_k ⟨g_k, diff⟩ / ⟨g_k, g_k⟩``. The two
inner products reduce over ALL of F, which an F-tiled carry grid cannot see
at once — so the op runs two passes over the same tiling: a dots kernel
(grid (B, K/Kt, F/Ft), F innermost, carrying the (Kt, 1) partial dots) and a
squared-grad accumulation kernel that reuses the riemann carry structure with
the per-(b, k) coefficient in place of the weight. Both passes stay
memory-bound single reads of g; g² is fused, never materialized in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _accum_kernel(acc_ref, g_ref, w_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc_ref[...].astype(jnp.float32)

    g = g_ref[...].astype(jnp.float32)  # (Kt, Ft)
    w = w_ref[...].astype(jnp.float32)  # (Kt, 1)
    o_ref[...] += jnp.sum(g * w, axis=0, keepdims=True)  # (1, Ft)


def _dots_kernel(g_ref, d_ref, s_ref, p_ref):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    g = g_ref[...].astype(jnp.float32)  # (Kt, Ft)
    d = d_ref[...].astype(jnp.float32)  # (1, Ft)
    s_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)  # (Kt, 1)
    p_ref[...] += jnp.sum(g * d, axis=1, keepdims=True)


def _accum_sq_kernel(acc_ref, g_ref, c_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc_ref[...].astype(jnp.float32)

    g = g_ref[...].astype(jnp.float32)  # (Kt, Ft)
    c = c_ref[...].astype(jnp.float32)  # (Kt, 1)
    o_ref[...] += jnp.sum((g * g) * c, axis=0, keepdims=True)  # (1, Ft)


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def idgi_dots_pallas(
    grads: jax.Array,
    diff: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """grads (B, K, F); diff (B, F) -> (⟨g,g⟩ (B, K) f32, ⟨g,diff⟩ (B, K) f32)."""
    B, K, F = grads.shape
    bk, bf = min(block_k, K), min(block_f, F)
    assert K % bk == 0 and F % bf == 0, (K, bk, F, bf)
    grid = (B, K // bk, F // bf)
    col = pl.BlockSpec((None, bk, 1), lambda b, k, f: (b, k, 0))
    s, p = pl.pallas_call(
        _dots_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bk, bf), lambda b, k, f: (b, k, f)),
            pl.BlockSpec((None, 1, bf), lambda b, k, f: (b, 0, f)),
        ],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((B, K, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(grads, diff[:, None, :])
    return s[..., 0], p[..., 0]


def _accum_call(kernel, acc, grads, per_step, block_k, block_f, interpret):
    """The riemann carry grid shared by the weighted and squared passes:
    acc (B, F) f32; grads (B, K, F); per_step (B, K) -> (B, F) f32."""
    B, K, F = grads.shape
    bk, bf = min(block_k, K), min(block_f, F)
    assert K % bk == 0 and F % bf == 0, (K, bk, F, bf)
    row = pl.BlockSpec((None, 1, bf), lambda b, f, k: (b, 0, f))
    out = pl.pallas_call(
        kernel,
        grid=(B, F // bf, K // bk),
        in_specs=[
            row,
            pl.BlockSpec((None, bk, bf), lambda b, f, k: (b, k, f)),
            pl.BlockSpec((None, bk, 1), lambda b, f, k: (b, k, 0)),
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, F), jnp.float32),
        interpret=interpret,
    )(acc[:, None, :], grads, per_step[:, :, None])
    return out[:, 0, :]


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def ig_accum_sq_pallas(
    acc: jax.Array,
    grads: jax.Array,
    coeff: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """acc (B, F) f32; grads (B, K, F); coeff (B, K) -> (B, F) f32.

    out = acc + Σ_k coeff_k · g_k² — the IDGI weighting pass (g² fused)."""
    return _accum_call(_accum_sq_kernel, acc, grads, coeff, block_k, block_f, interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def ig_accum_pallas(
    acc: jax.Array,
    grads: jax.Array,
    weights: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """acc (B, F) f32; grads (B, K, F); weights (B, K) -> (B, F) f32."""
    return _accum_call(_accum_kernel, acc, grads, weights, block_k, block_f, interpret)
