"""Every Pallas kernel compiles for a TPU v5e at the ViT-S/16 widths.

Interpret mode (the CPU default, ``kernels.common.default_interpret``) checks
what a kernel computes, not whether the TPU compiler accepts it: block shapes
off the (8, 128) tiling, 1-D vectors and VMEM budgets are only checked by
Mosaic. Each case lowers one kernel with ``interpret=False`` for a described
(not attached) v5e chip and asserts the program carries the kernel as a
``tpu_custom_call``.

Shapes are the ViT-S/16 explain path: 196 patch tokens × d_model 384 per
image (F = 75264 stage-2 features), an 8-image bucket with 16-step chunks,
6 heads of 64 with the token axis padded to the 128 flash tile (256), and a
LIME solve over 16 patch groups plus the intercept (N = 17 padded to 24).

The topology is described inside a module fixture, never at import time: one
process at a time may load the TPU library, and under several test workers
only the worker that runs this file should try.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, K, F = 8, 16, 196 * 384  # bucket rows, chunk steps, flattened features
NQ, SQ, D = 6, 256, 64  # heads, padded patch tokens, head dim
N_LIME = 24  # 16 groups + intercept, padded to the sublane multiple


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not describable here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _cases():
    from repro.kernels.flash_attention import kernel as fa
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ig_accum import kernel as iga
    from repro.kernels.interp_accum import kernel as ia
    from repro.kernels.interpolate.kernel import interpolate_pallas
    from repro.kernels.lstsq.kernel import wls_solve_pallas

    f32 = jnp.float32
    row, steps, tile = ((B, F), f32), ((B, K), f32), ((B, K, F), f32)
    qkv = ((B, NQ, SQ, D), f32)
    col = ((B, NQ, SQ, 1), f32)
    kvlen = ((B, 1), jnp.int32)

    def flash_vjp(q, k, v):
        # the op the model calls: model layout, 196 tokens padded inside,
        # custom-VJP backward through both backward kernels
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=False, interpret=False).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    no_interp = {"interpret": False}
    return {
        "interpolate": (
            lambda x, b, a: interpolate_pallas(x, b, a, **no_interp),
            [row, row, steps],
        ),
        "ig_accum": (
            lambda acc, g, w: iga.ig_accum_pallas(acc, g, w, **no_interp),
            [row, tile, steps],
        ),
        "ig_accum_sq": (
            lambda acc, g, c: iga.ig_accum_sq_pallas(acc, g, c, **no_interp),
            [row, tile, steps],
        ),
        "idgi_dots": (
            lambda g, d: iga.idgi_dots_pallas(g, d, **no_interp),
            [tile, row],
        ),
        "interp_add_bcast": (
            lambda x, b, a, u: ia.interp_add_pallas(x, b, a, u, **no_interp),
            [row, row, steps, row],
        ),
        "interp_add_step": (
            lambda x, b, a, u: ia.interp_add_pallas(x, b, a, u, **no_interp),
            [row, row, steps, tile],
        ),
        "accum_cot": (
            lambda g: ia.accum_cot_pallas(g, **no_interp),
            [tile],
        ),
        "flash_fwd": (
            lambda q, k, v, n: fa.flash_attention_fwd_pallas(
                q, k, v, n, causal=False, **no_interp
            ),
            [qkv, qkv, qkv, kvlen],
        ),
        "flash_bwd_dq": (
            lambda q, k, v, do, lse, dl, n: fa.flash_attention_bwd_dq_pallas(
                q, k, v, do, lse, dl, n, causal=False, **no_interp
            ),
            [qkv, qkv, qkv, qkv, col, col, kvlen],
        ),
        "flash_bwd_dkv": (
            lambda q, k, v, do, lse, dl, n: fa.flash_attention_bwd_dkv_pallas(
                q, k, v, do, lse, dl, n, causal=False, **no_interp
            ),
            [qkv, qkv, qkv, qkv, col, col, kvlen],
        ),
        "flash_vjp_model_layout": (
            flash_vjp,
            [((B, 196, NQ, D), f32)] * 3,
        ),
        "lstsq": (
            lambda a, r: wls_solve_pallas(a, r, **no_interp),
            [((B, N_LIME, N_LIME), f32), ((B, N_LIME), f32)],
        ),
    }


CASES = [
    "interpolate", "ig_accum", "ig_accum_sq", "idgi_dots", "interp_add_bcast",
    "interp_add_step", "accum_cot", "flash_fwd", "flash_bwd_dq",
    "flash_bwd_dkv", "flash_vjp_model_layout", "lstsq",
]


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
