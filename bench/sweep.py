#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate at
which the backlog does not grow through the window.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 20 --seed 7

One process sets the cell up once, then runs a window at each rate (same
weights, traffic drawn anew at that rate). Per rate it prints the requests
due, answered, still outstanding at the close, the p50/p95 latency, and the
mean latency of the window's first and last thirds: a last third far above
the first means the queue grew all through the window. A cell then offers
about four fifths of the knee, fixed in its mix file. The benchmark's own
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import numpy as np

    from bench.harness import loop, spec
    from bench.harness.measure import nearest_rank

    info = spec.cell(spec.load_benchmark(), args.workload)
    if info["traffic"]["arrivals"] != "open":
        print("the sweep is for open-loop cells", file=sys.stderr)
        return 2
    run = loop.CellRun(info, args.seed, args.seconds)
    t0 = time.perf_counter()
    run.setup()
    print(f"setup_s={time.perf_counter() - t0:.1f}", file=sys.stderr, flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        run.traffic["rate_per_s"] = rate
        run.reseed(args.seed)
        win = run.window()
        late = len(run._live)
        run.drain(win)
        lat = [r.latency for r in win.records]
        third = args.seconds / 3
        first = [r.latency for r in win.records if r.due < third]
        last = [r.latency for r in win.records if r.due >= 2 * third]
        print(json.dumps({
            "rate": rate, "due": len(win.records),
            "answered_in_window": sum(r.ok and r.finish <= win.elapsed for r in win.records),
            "outstanding_at_close": late,
            "p50_ms": 1e3 * nearest_rank(lat, 0.5), "p95_ms": 1e3 * nearest_rank(lat, 0.95),
            "first_third_mean_ms": 1e3 * float(np.mean(first)) if first else None,
            "last_third_mean_ms": 1e3 * float(np.mean(last)) if last else None,
            "rows_per_call": _rows_per_call(win),
        }), flush=True)
    return 0


def _rows_per_call(win) -> float:
    b0, b1 = win.stats_before.buckets, win.stats_after.buckets
    calls = sum(v.calls - (b0[k].calls if k in b0 else 0) for k, v in b1.items())
    rows = sum(v.requests - (b0[k].requests if k in b0 else 0) for k, v in b1.items())
    return rows / calls if calls else 0.0


if __name__ == "__main__":
    raise SystemExit(main())
