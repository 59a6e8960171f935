"""Fused interpolated-batch generation (stage 2 hot loop, memory-bound).

Naive IG materializes K interpolants with K× HBM reads of (x, baseline); this
kernel reads each (x, baseline) feature tile into VMEM **once** per K-tile and
streams the K interpolants out — HBM traffic drops from 2·K·F reads to
2·(K/Kt)·F, i.e. the read side is amortized over the whole α-tile.

Grid: (B, K/Kt, F/Ft). BlockSpecs keep every operand in VMEM with the batch
dim squeezed, so each block's last two dims satisfy the TPU tiling rule
(multiples of (8, 128) or the full array dims): x/baseline ride as (B, 1, F)
with (1, Ft) tiles, alphas as (B, K, 1) with (Kt, 1) tiles, out (Kt, Ft).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interp_kernel(x_ref, b_ref, a_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (1, Ft)
    b = b_ref[...].astype(jnp.float32)  # (1, Ft)
    a = a_ref[...].astype(jnp.float32)  # (Kt, 1)
    o_ref[...] = (b + a * (x - b)).astype(o_ref.dtype)  # (Kt, Ft)


@functools.partial(jax.jit, static_argnames=("block_k", "block_f", "interpret"))
def interpolate_pallas(
    x: jax.Array,
    baseline: jax.Array,
    alphas: jax.Array,
    *,
    block_k: int = 8,
    block_f: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """x, baseline: (B, F); alphas: (B, K) -> (B, K, F)."""
    B, F = x.shape
    K = alphas.shape[1]
    bk, bf = min(block_k, K), min(block_f, F)
    assert K % bk == 0 and F % bf == 0, (K, bk, F, bf)
    grid = (B, K // bk, F // bf)
    row = pl.BlockSpec((None, 1, bf), lambda b, k, f: (b, 0, f))
    return pl.pallas_call(
        _interp_kernel,
        grid=grid,
        in_specs=[row, row, pl.BlockSpec((None, bk, 1), lambda b, k, f: (b, k, 0))],
        out_specs=pl.BlockSpec((None, bk, bf), lambda b, k, f: (b, k, f)),
        out_shape=jax.ShapeDtypeStruct((B, K, F), x.dtype),
        interpret=interpret,
    )(x[:, None, :], baseline[:, None, :], alphas[:, :, None])
