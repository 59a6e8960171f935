"""Roofline machinery: HLO collective parsing, costing mode, report math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES_BY_NAME
from repro.models.common import COSTING, costing_mode, scan_or_unroll
from repro.roofline import (
    HW_V5E,
    hardware_for,
    model_flops,
    parse_collective_bytes,
    roofline_report,
)

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), replica_groups={}
  %ag = f32[256,256]{1,0} all-gather(%ar), dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %add = f32[128,256]{1,0} add(%ar, %cp)
  ROOT %rs = f32[16,256]{1,0} reduce-scatter(%add), dimensions={0}
}
"""


@pytest.mark.parametrize(
    "kind,name", [("TPU v5 lite", "tpu_v5e"), ("cpu", "cpu_host")]
)
def test_hardware_for_known_kinds(kind, name):
    assert hardware_for(kind).name == name


@pytest.mark.parametrize("kind", ["unknown", "TPU v4", "NVIDIA H100"])
def test_hardware_for_unknown_kind_raises(kind):
    with pytest.raises(ValueError, match="no hardware model"):
        hardware_for(kind)


def test_parse_collective_bytes_kinds():
    out = parse_collective_bytes(HLO_SAMPLE)
    b = 128 * 256 * 4
    assert out["all-reduce"] == b
    assert out["all-gather"] == b  # operand (the all-reduce result), not output
    assert out["collective-permute"] == b
    assert out["reduce-scatter"] == b
    assert out["total"] == 4 * b


def test_parse_ignores_non_collectives():
    out = parse_collective_bytes("%x = f32[4]{0} add(%a, %b)")
    assert out["total"] == 0


def test_parse_async_start_counted_once():
    hlo = """
  %p0 = f32[64]{0} parameter(0)
  %s = f32[64]{0} all-reduce-start(%p0)
  %d = f32[64]{0} all-reduce-done(%s)
"""
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"] == 64 * 4


# ------------------------------------------------------------ costing mode


def test_costing_mode_unrolls_scan_flops():
    def body(c, _):
        return c @ c, None

    def g(x):
        y, _ = scan_or_unroll(body, x, None, length=8)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    # fresh lambdas: jit caches lowering per function object, and the COSTING
    # flag is read at trace time
    from repro.roofline import cost_analysis_dict

    flops_scan = cost_analysis_dict(jax.jit(lambda v: g(v)).lower(x).compile())["flops"]
    with costing_mode():
        flops_unroll = cost_analysis_dict(
            jax.jit(lambda v: g(v)).lower(x).compile()
        )["flops"]
    assert flops_unroll > 6 * flops_scan  # 8 trips vs body-once


def test_scan_or_unroll_equivalence():
    def body(c, x):
        return c + x, c * 2

    xs = jnp.arange(5.0)
    c1, y1 = jax.lax.scan(body, jnp.asarray(0.0), xs)
    with costing_mode():
        c2, y2 = scan_or_unroll(body, jnp.asarray(0.0), xs)
    np.testing.assert_allclose(float(c1), float(c2))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_costing_mode_restores_flag():
    assert not COSTING
    with costing_mode():
        from repro.models import common

        assert common.COSTING
    from repro.models import common

    assert not common.COSTING


# ------------------------------------------------------------ report math


def test_model_flops_train_vs_decode():
    cfg = ARCHS["llama3-8b"]
    tr = model_flops(cfg, SHAPES_BY_NAME["train_4k"])
    de = model_flops(cfg, SHAPES_BY_NAME["decode_32k"])
    n = cfg.param_count()
    np.testing.assert_allclose(tr, 6 * n * 256 * 4096, rtol=1e-6)
    np.testing.assert_allclose(de, 2 * n * 128, rtol=1e-6)


def test_model_flops_moe_uses_active_params():
    cfg = ARCHS["qwen3-moe-30b-a3b"]
    assert cfg.active_param_count() < cfg.param_count() / 5
    f = model_flops(cfg, SHAPES_BY_NAME["train_4k"])
    np.testing.assert_allclose(f, 6 * cfg.active_param_count() * 256 * 4096, rtol=1e-6)


def test_roofline_report_terms():
    rep = roofline_report(
        arch="a",
        shape="train_4k",
        mesh_name="m",
        chips=256,
        cost={"flops": 197e12, "bytes accessed": 819e9},
        coll_bytes_per_chip=50e9,
        mflops=197e12 * 256 * 0.5,
    )
    np.testing.assert_allclose(rep.compute_s, 1.0)
    np.testing.assert_allclose(rep.memory_s, 1.0)
    np.testing.assert_allclose(rep.collective_s, 1.0)
    np.testing.assert_allclose(rep.useful_flops_ratio, 0.5)
    np.testing.assert_allclose(rep.roofline_fraction, 0.5)
    assert rep.dominant in ("compute", "memory", "collective")


def test_param_counts_match_published_sizes():
    """Sanity: analytic param counts land near the advertised model sizes."""
    expect = {
        "llama3-8b": (7.0e9, 9.0e9),
        "gemma3-27b": (25e9, 30e9),
        "qwen3-moe-30b-a3b": (28e9, 32e9),
        "qwen3-moe-235b-a22b": (220e9, 250e9),
        "mamba2-780m": (0.7e9, 0.9e9),
        "jamba-v0.1-52b": (49e9, 56e9),
        "internlm2-20b": (17e9, 22e9),
        "yi-9b": (8e9, 10e9),
        "internvl2-26b": (18e9, 28e9),  # backbone only (frontend stubbed)
        "whisper-tiny": (2e7, 7e7),  # untied embeddings + per-layer cross-attn
    }
    for arch, (lo, hi) in expect.items():
        n = ARCHS[arch].param_count()
        assert lo <= n <= hi, (arch, n / 1e9)
