#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. Set-up (JAX
start-up, weights from the seed, engine, inputs, warm-up of every shape the
cell's traffic uses) is timed as ``setup_s``; then the traffic runs for
``--seconds``. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window is traced and the result carries the
per-layer metrics, ``device.busy_s`` / ``window_s`` and a ``breakdown``.
Every run holds a sample of its answers to the plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown``), ``checks``. The numbers
compared also end stderr, each beside its limit.

Exit codes: 0 done; 2 the program's source is not in this checkout; 3 no
TPU, or fewer chips than the cell asks for (no result is printed).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program source at {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench.harness import spec

    chips = spec.find(spec.load_benchmark()["workloads"], args.workload, "workload")["chips"]
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"JAX found no usable backend: {e}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports platform {devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} chips, JAX has {len(devices)}", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.harness import runner

    out = runner.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, log=lambda s: print(s, flush=True))
    runner.print_result(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
