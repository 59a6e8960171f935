"""Integration: cell construction lowers+compiles on a real multi-device mesh.

Runs in a SUBPROCESS with xla_force_host_platform_device_count=8 so the main
test process keeps its single CPU device (the dry-run contract).
"""
import json

import pytest

_SCRIPT = r"""
import json
import jax, jax.numpy as jnp
from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeConfig
from repro.launch.cells import build_cell, lower_cell
from repro.launch.mesh import make_debug_mesh
from repro.models.common import costing_mode
from repro.roofline import cost_analysis_dict, parse_collective_bytes

mesh = make_debug_mesh(2, 4)
out = {}
cases = [
    ("llama3-8b", ShapeConfig("t", 64, 8, "train"), {"microbatches": 2}),
    ("qwen3-moe-30b-a3b", ShapeConfig("t", 64, 8, "train"), {"microbatches": 1}),
    ("mamba2-780m", ShapeConfig("d", 256, 8, "decode"), {}),
    ("gemma3-27b", ShapeConfig("p", 256, 8, "prefill"), {}),
    ("whisper-tiny", ShapeConfig("d", 256, 8, "decode"), {}),
]
for arch, shape, kw in cases:
    cfg = reduced(ARCHS[arch])
    with mesh:
        cell = build_cell(cfg, shape, mesh, **kw)
        compiled = lower_cell(cell).compile()
        cost = cost_analysis_dict(compiled)
        with costing_mode():
            kw2 = dict(kw); kw2.pop("microbatches", None)
            cell2 = build_cell(cfg, shape, mesh, **kw2)
            cost2 = cost_analysis_dict(lower_cell(cell2).compile())
    out[f"{arch}:{shape.kind}"] = {
        "flops": cost.get("flops", 0),
        "costing_flops": cost2.get("flops", 0),
        "collectives": parse_collective_bytes(compiled.as_text())["total"],
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def cell_results(run_child):
    out = run_child(_SCRIPT, "--xla_force_host_platform_device_count=8", timeout=1200)
    return json.loads(out.strip().splitlines()[-1])


def test_cells_compile_on_multi_device_mesh(cell_results):
    assert len(cell_results) == 5
    for k, v in cell_results.items():
        assert v["flops"] > 0, k


def test_costing_mode_counts_more_flops(cell_results):
    """Unrolled costing flops >= scanned flops (scan bodies counted once)."""
    for k, v in cell_results.items():
        assert v["costing_flops"] >= 0.9 * v["flops"], (k, v)


def test_train_cell_has_collectives(cell_results):
    assert cell_results["llama3-8b:train"]["collectives"] > 0
