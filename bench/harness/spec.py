"""Find a cell, its configuration, its traffic mix and its metrics by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

    bench/configs/<config>.json    sizes and engine settings, as run
    bench/configs/<config>.py      weights, inputs, plain reference, FLOPs
    bench/traffic/<traffic>.json   the traffic mix's parameters
    bench/metrics/<metric>.py      one reader per metric

so a later change adds a configuration, a mix or a metric as new files and
new entries, without editing a file that is already there.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as fh:
        return json.load(fh)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} (known: {known})")


def load_module(path: Path) -> ModuleType:
    """Import a file by path: names such as ``vit-s16`` or ``mfu.poisson``
    are not Python identifiers, so the files are loaded, not imported."""
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    mod_name = mod_name.replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_files(bm: dict, config_name: str) -> tuple[dict, dict, ModuleType]:
    """(BENCHMARK.json entry, sizes file, reference module) of a configuration."""
    entry = find(bm["configs"], config_name, "configuration")
    path = ROOT / entry["file"]
    with open(path) as fh:
        sizes = json.load(fh)
    return entry, sizes, load_module(path.with_suffix(".py"))


def traffic_file(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as fh:
        return json.load(fh)


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def cell_metrics(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a cell reports: end-to-end ones without ``--trace``,
    per-layer ones with it. An entry with ``workloads`` belongs to those
    cells; a per-layer entry without it belongs to every cell that reports
    the end-to-end metric it moves."""
    e2e = [m for m in bm["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in bm["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]


def cell(bm: dict, name: str) -> dict[str, Any]:
    """Everything a run of one cell needs, read from the files by name."""
    wl = find(bm["workloads"], name, "workload")
    entry, sizes, module = config_files(bm, wl["config"])
    return {
        "workload": wl,
        "config_entry": entry,
        "sizes": sizes,
        "model": module,
        "traffic": traffic_file(wl["traffic"]),
    }
