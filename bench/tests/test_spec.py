"""BENCHMARK.json names configurations, mixes, limits and metrics; each is a
file of its own that the harness finds by that name."""
import json

import pytest

from bench.harness import spec
from bench.harness.runner import limits_for

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    info = spec.cell(BM, cell)
    sizes, model = info["sizes"], info["model"]
    assert sizes["name"] == info["workload"]["config"]
    for fn in ("program_config", "init_params", "make_inputs", "ref_inputs", "logprob",
               "flops_forward", "flops_vjp", "flops_embed", "param_bytes", "small",
               "layer_kinds"):
        assert callable(getattr(model, fn)), fn
    assert model.CONTROL in ("bf16", "fp8")
    assert info["traffic"]["arrivals"] in ("open", "closed")
    lim = limits_for(cell)
    assert lim["sample"] >= 1 and lim["limits"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in spec.cell_metrics(BM, cell, trace=False)}
    layer = spec.cell_metrics(BM, cell, trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("name", [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(spec.metric_reader(name).read)


def test_config_files_hold_their_sizes():
    for entry in BM["configs"]:
        with open(spec.ROOT / entry["file"]) as fh:
            sizes = json.load(fh)
        assert sizes["name"] == entry["name"]
        assert sizes["source"] == entry["source"]
        assert sizes["reduced"] == entry["reduced"]


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.find(BM["workloads"], "no-such-cell", "workload")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
