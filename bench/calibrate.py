#!/usr/bin/env python3
"""Readings for a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 \
        [--control-seeds 3] [--sample N]

For each seed, in one process (the engine is built once; each seed brings
its own weights and traffic): a short window at the cell's own load, then
the sample of answers a run compares, held to the reference -- the
program's readings. For the first ``--control-seeds`` seeds the same
requests are also answered by the control (the reference one precision
below the configuration's) and held to the reference the same way -- the
control's readings -- and by the plain reference at the precision the
configuration serves in -- the ``plain`` readings, a witness of what that
precision alone departs by. A limit lies above every sound reading and
below the control's; ``PERF.md`` gives the readings each limit was set
from.

One JSON line per seed on stdout: ``{"seed", "program": {number: worst},
"control": {...}, "plain": {...}, "rows": [...]}``. The benchmark's own
runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--sample", type=int, default=0, help="0: the cell's own sample size")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench.harness import loop, reference as ref, runner, spec

    info = spec.cell(spec.load_benchmark(), args.workload)
    model, sizes, traffic = info["model"], info["sizes"], info["traffic"]
    lim = runner.limits_for(args.workload)
    n = args.sample or lim["sample"]
    slack = lim["limits"].get("endpoint_err", 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    run = loop.CellRun(info, seeds[0], args.seconds)
    t0 = time.perf_counter()
    run.setup()
    print(f"setup_s={time.perf_counter() - t0:.1f}", file=sys.stderr, flush=True)
    for k, seed in enumerate(seeds):
        if k:
            run.reseed(seed)
        win = run.window()
        run.drain(win)
        chosen = runner.sample(run.results(win), n, seed)
        line = {"seed": seed, "answered": len(chosen), "program": {}, "control": {},
                "plain": {}, "rows": []}
        for rec, inp, got in chosen:
            S = runner.seq_bucket(sizes, len(inp["tokens"]))
            row = ref.Plain(model, sizes, run.params, inp, "f32")
            entry = {"program": ref.compare(row, traffic, S, got, slack),
                     "m_used": got.get("m_used"),
                     "len": len(inp["tokens"]), "f_span": abs(got["f_x"] - got["f_baseline"])}
            if k < args.control_seeds:
                for side, mode in (("control", model.CONTROL),
                                   ("plain", ref.served_mode(sizes))):
                    ans = ref.explain(ref.Plain(model, sizes, run.params, inp, mode), traffic, S)
                    entry[side] = ref.compare(row, traffic, S, ans, slack)
            line["rows"].append(entry)
            for side in ("program", "control", "plain"):
                for name, v in entry.get(side, {}).items():
                    line[side][name] = max(line[side].get(name, 0.0), v)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
