"""Serving driver: batched generation, optionally with explain riding along.

    # classic: batched greedy generation on a reduced config
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --tokens 32

    # sampled decoding (exercises the non-greedy serve path)
    PYTHONPATH=src python -m repro.launch.serve --sample --temperature 0.8

    # unified mixed workload: generate + explain through ONE scheduler
    # (docs/serving.md) — prints per-SLO-class latency and queue stats
    PYTHONPATH=src python -m repro.launch.serve --mixed --tokens 8 --requests 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, reduced
from repro.models.registry import Model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import ServeEngine


def run_classic(cfg, params, args) -> int:
    model_batch = args.batch
    key = jax.random.PRNGKey(args.seed + 1)
    batch = {
        "tokens": jax.random.randint(
            key, (model_batch, args.prompt_len), 0, cfg.vocab_size
        )
    }
    if cfg.frontend == "vision":
        batch["frontend"] = jnp.ones(
            (model_batch, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16
        )
    if cfg.frontend == "audio":
        batch["frontend"] = jnp.ones(
            (model_batch, cfg.encoder_seq, cfg.frontend_dim), jnp.bfloat16
        )

    engine = ServeEngine(cfg, params, max_len=args.prompt_len + args.tokens)
    sample_kw = {}
    if args.sample:
        sample_kw = {
            "key": jax.random.PRNGKey(args.seed + 2),
            "temperature": args.temperature,
        }
    t0 = time.time()
    out = engine.generate(batch, args.tokens, **sample_kw)
    dt = time.time() - t0
    mode = f"sampled T={args.temperature}" if args.sample else "greedy"
    print(f"arch={cfg.name} {mode} generated {out.shape} in {dt:.2f}s")
    print("first sequence:", np.asarray(out[0])[:16], "...")
    assert not bool(jnp.any(out < 0)) and not bool(jnp.any(out >= cfg.vocab_size))
    return 0


def run_mixed(cfg, params, args) -> int:
    """Mixed generate+explain traffic through the unified MixedScheduler."""
    from repro.serve import (
        BATCH,
        INTERACTIVE,
        ExplainEngine,
        ExplainRequest,
        GenerateRequest,
        MixedScheduler,
        TenantPolicy,
    )

    # probe-reuse bit-exactness holds at f32 compute (docs/serving.md)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    engine = ExplainEngine(
        cfg,
        params,
        m=args.m,
        n_int=args.n_int,
        seq_buckets=(8, 16, 32, 64),
        adaptive=args.adaptive,
        tol=args.tol,
        result_cache=args.result_cache * (1 << 20),
    )
    max_len = args.prompt_len + args.tokens
    tenants = (
        {"default": TenantPolicy(rate=args.tenant_rate)} if args.tenant_rate else None
    )
    sched = MixedScheduler(
        engine,
        max_len=max_len,
        max_queue=args.max_queue,
        decode_chunk=args.decode_chunk,
        tenants=tenants,
    )
    rng = np.random.default_rng(args.seed)

    for rnd in range(args.rounds):
        tickets = []
        for i in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size, args.prompt_len).astype(np.int32)
            if i % 3 == 2:  # every third request is explain-only traffic
                tickets.append(
                    sched.submit(
                        ExplainRequest(
                            tokens=prompt, target=int(rng.integers(0, cfg.vocab_size))
                        )
                    )
                )
            else:
                tickets.append(
                    sched.submit(
                        GenerateRequest(
                            tokens=prompt,
                            num_tokens=args.tokens,
                            explain=True,
                            slo=INTERACTIVE if i % 2 == 0 else BATCH,
                            temperature=args.temperature if args.sample else 0.0,
                            seed=args.seed + i if args.sample else None,
                        )
                    )
                )
        t0 = time.perf_counter()
        sched.run_until_idle()
        wall = time.perf_counter() - t0
        done = sum(t.status == "done" for t in tickets)
        print(
            f"round {rnd}: {done}/{len(tickets)} done in {wall:.2f}s "
            f"(degraded={engine.stats.degraded} "
            f"rejected={sched.rejected_backpressure + sched.rejected_rate})"
        )

    st = engine.stats
    print(f"executable cache: hits={st.hits} misses={st.misses} "
          f"hit_rate={st.hit_rate:.2f}")
    if engine.result_cache is not None:
        print(f"result cache: hits={st.result_hits} misses={st.result_misses} "
              f"hit_rate={st.result_hit_rate:.2f} evictions={st.result_evictions} "
              f"bytes={st.result_bytes}")
    print(f"scheduler: degraded={st.degraded} preempted={st.preempted} "
          f"stragglers={len(sched.monitor.flagged)}")
    for name, s in sorted(sched.latency_summary().items()):
        print(f"  {name:12s} n={s['n']:<4d} p50={1e3 * s['p50_s']:.1f}ms "
              f"p99={1e3 * s['p99_s']:.1f}ms")
    gen = next(t for t in tickets if t.kind == "generate" and t.status == "done")
    a0 = gen.attributions[0]
    print(f"sample generate ticket: tokens={gen.tokens[:8]} "
          f"first-token attribution f_x={a0['f_x']:.4f} delta={a0['delta']:.5f} "
          f"(endpoint donated by the decode prefill — no re-run)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=sorted(ARCHS))
    ap.add_argument("--published", action="store_true",
                    help="serve the config as published instead of the "
                    "CPU-sized reduced() variant")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sample", action="store_true",
                    help="categorical sampling instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed generate+explain traffic through the unified "
                    "MixedScheduler (docs/serving.md)")
    ap.add_argument("--requests", type=int, default=8, help="requests/round (--mixed)")
    ap.add_argument("--rounds", type=int, default=2, help="traffic rounds (--mixed)")
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--n-int", type=int, default=4)
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--tol", type=float, default=1e-2)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--tenant-rate", type=float, default=0.0,
                    help="per-tenant admission rate in req/s (0 = unlimited)")
    ap.add_argument("--result-cache", type=int, default=0, metavar="MB",
                    help="content-addressed attribution cache budget in MB "
                    "(0 = off): repeat explain traffic completes at admission "
                    "without a queue slot (--mixed; docs/caching.md)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.published:
        cfg = reduced(cfg)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.mixed:
        if args.prompt_len > 32:
            args.prompt_len = 16  # keep the demo's bucket set small
        return run_mixed(cfg, params, args)
    return run_classic(cfg, params, args)


if __name__ == "__main__":
    raise SystemExit(main())
