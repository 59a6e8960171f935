"""Explanation-serving driver — the paper's low-latency XAI under traffic.

    PYTHONPATH=src python -m repro.launch.explain --arch llama3-8b \
        --method idgi --schedule paper --m 64 --n-int 4 --requests 16 --rounds 3

Drives the shape-bucketed ExplainEngine with MIXED-LENGTH request traffic
(random prompt lengths in [--min-seq, --max-seq]): round 1 pays the per-bucket
compilations, later rounds ride the compiled-executable cache. Prints
per-bucket latency, compile time, and the cache hit-rate, then the chosen
schedule vs uniform convergence comparison at the same step budget.

Multi-device serving (DESIGN.md §9): ``--mesh dp,tp`` builds a
(data=dp, model=tp) mesh and shards the folded (batch × step) stage-2 axis
across the data axis. On a CPU-only host, ``--host-devices N`` forces N
virtual devices (it must win the race with backend init, so it is applied
before any jax call; the equivalent manual form is
``XLA_FLAGS=--xla_force_host_platform_device_count=N``):

    PYTHONPATH=src python -m repro.launch.explain --arch llama3-8b \
        --host-devices 4 --mesh 4,1 --requests 16 --rounds 3

``--method`` picks the attribution method from the ``repro.core.methods``
registry (see the table in ``--help``); ``--schedule`` picks the
interpolation schedule family — the two compose freely (DESIGN.md §8).

``--attn flash`` serves the model through the Pallas flash-attention
custom-VJP kernel (interpret mode on CPU) instead of materializing
attention; ``--workload`` picks what gets explained:

  traffic   mixed-length random token traffic (the default serving sweep)
  prompt    ONE fixed deterministic prompt — prints the per-token
            attribution table (LM prompt attribution)
  vit       ViT-S/16 on --requests synthetic images — patch-feature
            requests through the same bucketed engine; prints the top
            attributed patches on the patch grid (docs/attention.md)

Models are CPU-sized (``configs.reduced`` / ``reduced_vit``) unless
``--published`` asks for the config as published. ``run()`` is the whole
launcher as a function returning the engine and its last results;
``load_workload`` and ``make_engine`` are its construction code, shared
with ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, reduced
from repro.core.methods import METHODS
from repro.core.schedule import SCHEDULES
from repro.models.registry import Model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import ExplainEngine, ExplainRequest


def make_traffic(cfg, n: int, lo: int, hi: int, rng) -> list[ExplainRequest]:
    return [
        ExplainRequest(
            tokens=rng.integers(1, cfg.vocab_size, size=int(s)).astype(np.int32),
            target=int(rng.integers(0, cfg.vocab_size)),
        )
        for s in rng.integers(lo, hi + 1, size=n)
    ]


def methods_table() -> str:
    """The registry, rendered for --help (DESIGN.md §8)."""
    lines = ["attribution methods (--method):"]
    for name in sorted(METHODS):
        spec = METHODS[name]
        if spec.forward_only:
            extra = f" [forward-only, n_masks={spec.n_masks}]"
        elif spec.expand is not None:
            extra = f" [accum={spec.accum}, n_samples={spec.n_samples}]"
        else:
            extra = f" [accum={spec.accum}]"
        lines.append(f"  {name:14s} {spec.description}{extra}")
    lines.append("schedule families (--schedule): " + ", ".join(sorted(SCHEDULES)))
    return "\n".join(lines)


def report(engine: ExplainEngine) -> None:
    st = engine.stats
    print(f"  executable cache: hits={st.hits} misses={st.misses} "
          f"hit_rate={st.hit_rate:.2f}")
    if engine.result_cache is not None:
        print(f"  result cache: hits={st.result_hits} misses={st.result_misses} "
              f"hit_rate={st.result_hit_rate:.2f} evictions={st.result_evictions} "
              f"bytes={st.result_bytes}")
    if st.degraded or st.preempted or st.queue_depth:
        print(f"  scheduler: degraded={st.degraded} preempted={st.preempted} "
              f"queue_depth={st.queue_depth}")
    if engine.mesh is not None:
        print(f"  mesh: {dict(zip(engine.mesh.axis_names, engine.mesh.devices.shape))} "
              f"dp={engine.dp} mesh_fallbacks={st.mesh_fallbacks}")
    for shape in sorted(st.buckets):
        b = st.buckets[shape]
        print(
            f"  bucket B={shape[0]:<3d} S={shape[1]:<5d} calls={b.calls:<3d} "
            f"reqs={b.requests:<4d} compile={b.compile_s:.2f}s "
            f"mean_latency={1e3 * b.mean_latency_s:.1f}ms "
            f"bytes={b.bytes_accessed:.2e} peak={b.peak_bytes:.2e}"
        )
    for shape in sorted(st.hop_buckets):
        b = st.hop_buckets[shape]
        print(
            f"  hop    B={shape[0]:<3d} S={shape[1]:<5d} calls={b.calls:<3d} "
            f"{'':9s} compile={b.compile_s:.2f}s "
            f"mean_latency={1e3 * b.mean_latency_s:.1f}ms"
        )
    a = st.adaptive
    if a.requests:
        print(
            f"  adaptive: ladder={engine.m_ladder} converged={a.converged}/{a.requests} "
            f"early_exits={a.early_exits} hops={a.hop_calls} "
            f"mean_m_used={a.mean_m_used:.1f} steps={a.total_steps} "
            f"(launched {a.launched_steps} incl. pad) probe_fwd={a.probe_forwards}"
        )
        print(f"  m_used histogram: {dict(sorted(a.m_used.items()))}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=methods_table(),
    )
    ap.add_argument("--arch", default="llama3-8b", choices=sorted(ARCHS))
    ap.add_argument(
        "--published", action="store_true",
        help="serve the config as published (full widths and depth) instead "
        "of the CPU-sized reduced() / reduced_vit() variant",
    )
    ap.add_argument(
        "--method", default="ig", choices=sorted(METHODS),
        help="attribution method (see table below)",
    )
    ap.add_argument(
        "--schedule", default="paper", choices=sorted(SCHEDULES),
        help="interpolation schedule family",
    )
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--n-int", type=int, default=4)
    ap.add_argument(
        "--chunk", type=int, default=0,
        help="stage-2 steps per scan step (0 = all m at once); bounds the "
        "folded (B*chunk) batch, i.e. device memory",
    )
    ap.add_argument(
        "--n-masks", type=int, default=0,
        help="perturbation mask budget P for forward-only methods "
        "(occlusion/rise/lime; 0 = method default)",
    )
    ap.add_argument("--requests", type=int, default=16, help="requests per round")
    ap.add_argument("--rounds", type=int, default=3, help="traffic rounds (round 1 compiles)")
    ap.add_argument("--min-seq", type=int, default=9)
    ap.add_argument("--max-seq", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--adaptive",
        action="store_true",
        help="δ-feedback early-exit: escalate unconverged requests up the m-ladder",
    )
    ap.add_argument("--tol", type=float, default=1e-2, help="relative δ tolerance")
    ap.add_argument("--m-max", type=int, default=0, help="ladder top (default 8·m)")
    ap.add_argument(
        "--n-samples", type=int, default=0,
        help="path-ensemble size for noise_tunnel/expected_grad (0 = method default)",
    )
    ap.add_argument(
        "--sigma", type=float, default=0.0,
        help="ensemble perturbation scale (0 = method default)",
    )
    ap.add_argument(
        "--fused", action="store_true",
        help="fused stage 2: interpolation composed into the VJP (DESIGN.md §10)",
    )
    ap.add_argument(
        "--attn", default="auto", choices=("auto", "flash"),
        help="attention implementation: flash = Pallas custom-VJP kernel "
        "(O(S·D) backward residuals; interpret mode on CPU)",
    )
    ap.add_argument(
        "--workload", default="traffic", choices=("traffic", "prompt", "vit"),
        help="traffic = mixed-length token traffic; prompt = one fixed LM "
        "prompt with a per-token attribution table; vit = ViT-S/16 patch "
        "attribution over --requests synthetic images (ignores "
        "--arch/--min-seq/--max-seq)",
    )
    ap.add_argument(
        "--use-kernels", action="store_true",
        help="inject the Pallas kernel set (interpret-mode on CPU)",
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="load per-(bucket, device) tuned configs from results/autotune_<device>.json",
    )
    ap.add_argument(
        "--result-cache", type=int, default=0, metavar="MB",
        help="content-addressed attribution cache budget in MB (0 = off); "
        "repeat requests replay bit-identically without touching the engine "
        "(docs/caching.md)",
    )
    ap.add_argument(
        "--warm-state", default="", metavar="DIR",
        help="warm-start persistence directory: restore the AOT executable "
        "set (+ autotune entries + hop-zero history) before serving and "
        "save it after — a restarted process reaches its first explanation "
        "with zero compiles (docs/caching.md)",
    )
    ap.add_argument(
        "--hop-zero", action="store_true",
        help="with --adaptive: start each bucket at the δ-history quantile "
        "rung instead of the base rung (repeat traffic skips known hops)",
    )
    ap.add_argument(
        "--mesh", default="",
        help="'dp,tp' device mesh for sharded serving (e.g. 4,1); empty = single-device",
    )
    ap.add_argument(
        "--host-devices", type=int, default=0,
        help="force N virtual CPU devices (multi-device demo on one host)",
    )
    ap.add_argument(
        "--scheduler", action="store_true",
        help="route traffic through the MixedScheduler admission queue "
        "(bounded, per-tenant rate limits — docs/serving.md); prints "
        "backpressure/rate rejections and degradation counters",
    )
    ap.add_argument(
        "--max-queue", type=int, default=64,
        help="scheduler queue bound (with --scheduler)",
    )
    ap.add_argument(
        "--tenant-rate", type=float, default=0.0,
        help="per-tenant token-bucket refill rate in req/s "
        "(0 = unlimited; with --scheduler)",
    )
    return ap


@dataclass
class Workload:
    """What the launcher serves: the model, its params and the engine
    kwargs the workload needs; ``fixed_reqs`` is replayed every round
    (``None``: fresh mixed-length traffic per round)."""

    cfg: Any
    params: Any
    fixed_reqs: Optional[list[ExplainRequest]] = None
    engine_kwargs: dict = field(default_factory=dict)


def vit_requests(cfg, params, n: int, seed: int) -> list[ExplainRequest]:
    """``n`` synthetic images (uniform pixels from ``seed``), each explained
    for the class the model predicts."""
    from repro.models import vit

    imgs = jax.random.uniform(
        jax.random.PRNGKey(seed), (n, cfg.image_size, cfg.image_size, cfg.channels)
    )
    targets = np.asarray(jnp.argmax(jax.jit(vit.forward, static_argnums=0)(
        cfg, params, imgs), -1))
    feats = np.asarray(vit.patchify(cfg, imgs), np.float32)
    return [
        ExplainRequest(
            tokens=np.arange(cfg.num_patches, dtype=np.int32),
            target=int(t),
            features=f,
        )
        for f, t in zip(feats, targets)
    ]


def load_workload(args) -> Workload:
    """Config (published or CPU-sized), seeded random params, requests."""
    if args.workload == "vit":
        from repro.configs.vit import CONFIG, reduced_vit
        from repro.models import vit

        cfg = CONFIG if args.published else reduced_vit()
        params = vit.init(cfg, jax.random.PRNGKey(args.seed))
        reqs = vit_requests(cfg, params, args.requests, args.seed + 1)
        print(f"vit workload: {cfg.name} {len(reqs)} images x {cfg.num_patches} "
              f"patches, predicted classes {[r.target for r in reqs]}")
        return Workload(cfg, params, reqs, {"seq_buckets": (cfg.num_patches,)})
    cfg = get_config(args.arch)
    if not args.published:
        cfg = reduced(cfg)
    if cfg.frontend or cfg.is_encdec:
        print(f"note: {cfg.name} frontend is stubbed; explaining token stream only")
    params = Model(cfg).init(jax.random.PRNGKey(args.seed))
    fixed_reqs = None
    if args.workload == "prompt":
        # one DETERMINISTIC prompt: same tokens every run, target fixed —
        # the per-token table below is reproducible output
        prompt = (np.arange(1, 13, dtype=np.int32) * 7) % (cfg.vocab_size - 1) + 1
        fixed_reqs = [ExplainRequest(tokens=prompt, target=int(prompt[-1]))]
        print(f"prompt workload: tokens={prompt.tolist()} target={prompt[-1]}")
    return Workload(cfg, params, fixed_reqs)


def make_engine(args, wl: Workload, *, schedule: str = "", mesh=None, **overrides):
    """The launcher's ExplainEngine for ``args`` (``overrides`` win)."""
    kw = dict(
        method=args.method,
        schedule=schedule or args.schedule,
        m=args.m,
        n_int=args.n_int,
        chunk=args.chunk,
        mesh=mesh,
        adaptive=args.adaptive,
        tol=args.tol,
        m_max=args.m_max,
        n_samples=args.n_samples,
        sigma=args.sigma,
        n_masks=args.n_masks,
        fused=args.fused,
        use_kernels=args.use_kernels,
        attn=args.attn,
        autotune=args.autotune,
        result_cache=args.result_cache * (1 << 20),
        hop_zero=args.hop_zero,
        **wl.engine_kwargs,
    )
    kw.update(overrides)
    return ExplainEngine(wl.cfg, wl.params, **kw)


def make_scheduler(args, engine):
    """``--scheduler``: the MixedScheduler admission queue (per-row
    methods only), else None."""
    if not args.scheduler:
        return None
    if engine.n_samples > 1:
        print("note: --scheduler serves per-row methods only; "
              f"{args.method} (n_samples={engine.n_samples}) runs direct")
        return None
    from repro.serve import MixedScheduler, TenantPolicy

    tenants = (
        {"default": TenantPolicy(rate=args.tenant_rate)} if args.tenant_rate else None
    )
    return MixedScheduler(engine, max_queue=args.max_queue, tenants=tenants)


def serve_round(engine, sched, reqs) -> list[dict]:
    """One round of requests, direct or through the scheduler; returns the
    results of the requests that were served."""
    if sched is None:
        return engine.explain(reqs)
    tickets = [sched.submit(r) for r in reqs]
    sched.run_until_idle()
    rej = sum(t.status.startswith("rejected") for t in tickets)
    if rej:
        print(f"  {rej} rejected (backpressure={sched.rejected_backpressure} "
              f"rate={sched.rejected_rate})")
    return [t.result for t in tickets if t.result is not None]


def run(argv: Optional[Sequence[str]] = None) -> tuple[ExplainEngine, list[dict]]:
    """The launcher end to end: returns the ``--schedule`` engine and the
    results of its last round (uniform comparison engines only print)."""
    args = build_parser().parse_args(argv)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import ensure_host_devices, make_explain_mesh, parse_mesh_arg

        dp, tp = parse_mesh_arg(args.mesh)
        ensure_host_devices(args.host_devices or dp * tp)
        mesh = make_explain_mesh(dp, tp)
        print(f"mesh: data={dp} model={tp} over {jax.device_count()} devices")

    wl = load_workload(args)
    cfg = wl.cfg
    rng = np.random.default_rng(args.seed)
    compare = (args.schedule,) if args.schedule == "uniform" else (args.schedule, "uniform")
    if METHODS[args.method].forward_only:
        # perturbation methods never touch the interpolation schedule — one
        # pass, no uniform comparison leg
        compare = (args.schedule,)
    primary = None
    for sched_name in compare:
        engine = make_engine(args, wl, schedule=sched_name, mesh=mesh)
        # the warm state belongs to the primary --schedule engine only; the
        # sweep's comparison engines would just warn about a context mismatch
        if args.warm_state and sched_name == args.schedule:
            from repro.serve import load_warm_state

            rep = load_warm_state(engine, args.warm_state)
            if rep.restored:
                print(f"warm state: restored {rep.executables} executables "
                      f"via {rep.via}")
            else:
                print(f"warm state: cold start ({rep.reason})")
        if METHODS[args.method].forward_only:
            mode = f"P={engine.n_masks} masks (forward-only)"
        elif args.adaptive:
            mode = f"adaptive tol={args.tol} ladder={engine.m_ladder}"
        else:
            mode = f"m={args.m}"
        samples = f" samples={engine.n_samples}" if engine.n_samples > 1 else ""
        flags = (" fused" if args.fused else "") + (" kernels" if args.use_kernels else "") \
            + (" autotuned" if args.autotune else "")
        print(f"method={args.method} schedule={sched_name} {mode}{samples}{flags} "
              f"traffic={args.rounds}x{args.requests} reqs S∈[{args.min_seq},{args.max_seq}]")
        sched = make_scheduler(args, engine)
        out: list[dict] = []
        for rnd in range(args.rounds):
            reqs = (
                wl.fixed_reqs
                if wl.fixed_reqs is not None
                else make_traffic(cfg, args.requests, args.min_seq, args.max_seq, rng)
            )
            t0 = time.perf_counter()
            out = serve_round(engine, sched, reqs)
            wall = time.perf_counter() - t0
            if not out:
                print(f" round {rnd}: all {len(reqs)} requests rejected")
                continue
            deltas = [o["delta"] for o in out]
            line = (f" round {rnd}: wall={wall:.2f}s mean_delta={np.mean(deltas):.5f} "
                    f"max_delta={np.max(deltas):.5f}")
            if args.adaptive:
                line += (f" mean_m_used={np.mean([o.get('m_used', 0) for o in out]):.1f}"
                         f" conv={sum(o.get('converged', False) for o in out)}/{len(out)}")
            print(line)
        report(engine)
        if sched_name == args.schedule:
            primary = (engine, out)
            if args.warm_state:
                from repro.serve import save_warm_state

                save_warm_state(engine, args.warm_state)
                with open(os.path.join(args.warm_state, "manifest.json")) as fh:
                    n_saved = json.load(fh)["n_executables"]
                print(f"warm state: saved {n_saved} executables "
                      f"to {args.warm_state}")
    engine, out = primary
    if not out:
        return primary
    scores = np.asarray(out[0]["token_scores"])
    if args.workload == "prompt":
        print("per-token attribution (pos, token, score):")
        for i, (t, s) in enumerate(zip(wl.fixed_reqs[0].tokens, scores)):
            print(f"  {i:3d} {int(t):6d} {s:+.6f}")
    elif args.workload == "vit":
        g = cfg.image_size // cfg.patch_size
        grid = scores.reshape(g, g)
        flat = np.argsort(-np.abs(grid), axis=None)[:5]
        print(f"top-5 attributed patches on the {g}x{g} grid (row, col, score):")
        for idx in flat:
            r, c = divmod(int(idx), g)
            print(f"  ({r}, {c}) {grid[r, c]:+.6f}")
    else:
        top = np.argsort(-np.abs(scores))[:5]
        print("top-5 attributed positions (last round, req 0):", top)
    return primary


def main(argv: Optional[Sequence[str]] = None) -> int:
    enable_compile_cache()
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
