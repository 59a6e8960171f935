#!/usr/bin/env python3
"""Chip smoke test: the explain path at published widths on a TPU.

    python chip_smoke.py [--seed N]      # one chip: Phase A, then Phase B
    python chip_smoke.py --chips 4       # four chips: sharded vs one-chip ig

Phase A serves ViT-S/16 as published (``configs/vit.py``: 224 px, 16 px
patches = 196 tokens, d_model 384, 12 layers, 6 heads, f32) on 8 synthetic
images through ``ExplainEngine``: ``ig`` (paper schedule, adaptive, tol 1e-2,
m 16 up to 128), ``idgi`` at m 32 and ``occlusion``. It then serves the
``ig`` requests again on the kernel path (Pallas flash attention, fused stage
2, Pallas accumulators) and compares them with the XLA path.

Phase B serves ``mamba2-780m`` as published (48 layers, d_model 1536, ssm
state 128, vocab 50,280) on 4 prompts of 129-256 tokens through
``ExplainEngine`` behind ``MixedScheduler``: ``ig``, paper schedule, m 16.
It then serves the same prompts on the uniform schedule and holds one of
them to a plain per-step ``jax.grad`` reference at the same nodes.

``--chips 4`` runs only Phase A's ``ig`` traffic, fixed-m and adaptive,
through an engine on a (data=4) mesh and through a one-chip engine in the
same process, and compares them (DESIGN.md §9).

Engines are built by the explain launcher's own construction code
(``repro.launch.explain.load_workload`` / ``make_engine``). Weights are
random and every input comes from ``--seed``. Each phase fails the run on:
a non-finite δ, a degraded ticket, a mesh fallback, an executable-cache miss
in a second identical round, an executable whose ``memory_analysis()`` peak
exceeds the device's ``bytes_limit``, a kernel-path executable without
``tpu_custom_call``, or attributions outside the tolerance written beside
each comparison. Earlier lines report compile seconds, the warm round's wall
time, mean ``m_used``, δ/|f(x) − f(x')|, the executables' peak bytes and the
device's ``peak_bytes_in_use``. The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``.

It exits non-zero and prints no result when JAX finds no TPU, or when the
repository's ``src/`` is not beside this file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# stage-2 steps per scan step, sized from ``compiled.memory_analysis()`` of
# each executable compiled for a described v5e chip (16 GB HBM):
#   ViT-S/16, B=8: chunk 8 -> 8.1 GB (XLA path), 9.0 GB (kernel path);
#                  chunk 16 -> 15.9 GB, too close to the chip's 15.75 GB.
#   mamba2-780m, B=4, S=256: without remat even chunk 1 needs 18 GB (the SSD
#                  intra-chunk tensors of 48 layers kept for the backward),
#                  so the engine compiles it with remat (it measures that
#                  itself); chunk 8 -> 8.4 GB (3.1 GB of it params).
VIT_CHUNK = 8
MAMBA_CHUNK = 8

# Kernel path vs XLA path (ig, f32 compute). XLA's default f32 matmul on a
# TPU is one bf16 pass (2^-9 relative per product); the flash kernel and the
# fused stage 2 order their f32 sums differently, so the two paths may
# differ by several bf16 roundings carried through 12 layers and the
# backward pass. 2e-2 of a row's largest |token score| bounds that and still
# fails a wrong tile, mask or scale, which move scores by O(1) of that max.
KERNEL_RTOL = 2e-2
# Sharded vs one-chip. DESIGN.md §9's bit-identity holds where f32 matmuls
# are exact f32 (the CPU tests). On a TPU the default f32 matmul is one
# bf16 pass, and the sharded program is a different compiled program
# (per-device batch 2 instead of 8, the folded batch resharded with
# all-to-all), so its roundings fall elsewhere: the first four-chip run
# measured 1.5e-3 of the row max at fixed m. 1e-2 bounds that and still
# fails a misrouted or misweighted row, which moves scores by O(1).
MESH_RTOL = 1e-2
# An adaptive row exits at the first rung where δ <= tol·|f(x) − f(x')|.
# Where one path's δ sits just under that threshold and the other's just
# over, the two exit at different rungs. Such a row is served again with
# the slower path's ladder capped at the faster path's rung and compared
# there like every other row; the slower path's δ there must lie within
# FLIP_FRAC of the threshold above it. The first chip run had one such row
# (kernel path vs XLA path, rung 64): δ 5.4% under and 10.5% over its
# threshold, 1.6e-3 of |f(x) − f(x')| apart, inside the 2e-2 the paths are
# held to. A quarter threshold bounds that with room, and still fails a
# path whose δ is wrong, which lands O(1) thresholds away.
FLIP_FRAC = 0.25
# Phase B, engine vs a plain per-node jax.grad at the same uniform nodes, at
# the config's bf16 compute and at f32. The engine's program (4 rows, 8
# nodes per scan step, remat, padded to 256 tokens) rounds differently from
# a one-row, one-node program, and bf16 keeps 8 significant bits through 48
# layers. Scores: 5e-2 of the row's largest |score| (the first chip run
# measured 8.2e-3 against bf16, 5.1e-3 against f32); a wrong gradient moves
# them by O(1) of it.
MAMBA_REF_RTOL = 5e-2
# δ, as a fraction of |f(x) − f(x')|: with random weights at published
# widths the logits are about 1e3 in size, where one bf16 rounding is 4 to
# 8, and f(x), f(x') are read from them; over |f(x) − f(x')| of about 57
# that moves δ by up to about 0.15 (the first chip run measured 0.032
# against bf16, 0.060 against f32).
MAMBA_REF_DELTA = 0.25


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def launcher_args(extra: list[str]):
    from repro.launch import explain

    return explain.build_parser().parse_args(extra)


def device_bytes() -> tuple[int, int]:
    """(peak_bytes_in_use, bytes_limit) of the first device."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_limit", 0))


def _bucket_stats(engine):
    st = engine.stats
    return [b for d in (st.buckets, st.hop_buckets) for b in d.values()]


def rel_delta(o: dict) -> float:
    """δ as a fraction of |f(x) − f(x')|, the quantity it is a gap in."""
    return o["delta"] / max(abs(o["f_x"] - o["f_baseline"]), 1e-30)


def serve_twice(name: str, engine, reqs, sched=None) -> list[dict]:
    """Round 1 compiles and serves; round 2 replays the same requests and
    must compile nothing. Checks δ, degradation, mesh fallbacks and every
    executable's memory against the device."""
    import numpy as np

    from repro.launch.explain import serve_round

    t0 = time.perf_counter()
    serve_round(engine, sched, reqs)
    cold = time.perf_counter() - t0
    misses = engine.stats.misses
    t0 = time.perf_counter()
    out = serve_round(engine, sched, reqs)
    warm = time.perf_counter() - t0
    st = engine.stats
    deltas = np.asarray([o["delta"] for o in out], np.float64)
    if engine._spec.forward_only:
        budget = f"masks={engine.n_masks}"
    else:
        budget = (f"mean_m_used={np.mean([o.get('m_used', engine.m) for o in out]):.2f} "
                  f"max_delta_rel={max(rel_delta(o) for o in out):.6g}")
    bstats = _bucket_stats(engine)
    exec_peak = max(b.peak_bytes for b in bstats)
    in_use, limit = device_bytes()
    print(
        f"[{name}] compile_s={sum(b.compile_s for b in bstats):.2f} cold_round_s={cold:.3f} "
        f"warm_round_s={warm:.4f} requests={len(out)} {budget} "
        f"max_delta={deltas.max():.6g} misses={misses} replay_misses={st.misses - misses} "
        f"degraded={st.degraded} mesh_fallbacks={st.mesh_fallbacks} "
        f"executable_peak_bytes={exec_peak:.0f} bytes_limit={limit} "
        f"remat_shapes={sum(b.remat for b in bstats)} peak_bytes_in_use={in_use}",
        flush=True,
    )
    if len(out) != len(reqs):
        fail(f"{name}: {len(reqs) - len(out)} requests got no result")
    if not np.isfinite(deltas).all():
        fail(f"{name}: non-finite delta {deltas}")
    if st.degraded:
        fail(f"{name}: {st.degraded} degraded tickets")
    if st.mesh_fallbacks:
        fail(f"{name}: {st.mesh_fallbacks} mesh fallbacks")
    if st.misses != misses:
        fail(f"{name}: replay round compiled {st.misses - misses} executables")
    if not limit or exec_peak > limit:
        fail(f"{name}: an executable needs {exec_peak:.0f} bytes, the device "
             f"allows {limit}")
    return out


def compare(name: str, got: list[dict], ref: list[dict], rtol: float, rerun=None) -> None:
    """Per-row attribution agreement within ``rtol`` of the row's max
    |score|, and δ within ``rtol`` of |f(x) − f(x')|.

    A row whose adaptive ladders exited at different rungs is compared at
    the lower rung: ``rerun(side, m)`` serves that side ("got" or "ref")
    again with its ladder capped at ``m``, and its δ there must lie within
    ``FLIP_FRAC`` of the threshold above it (see FLIP_FRAC)."""
    import numpy as np

    got, ref = list(got), list(ref)
    capped: dict = {}
    for i, (a, b) in enumerate(zip(got, ref)):
        if a.get("m_used") == b.get("m_used"):
            continue
        side, m, fast = ("got", b["m_used"], b) if a["m_used"] > b["m_used"] else (
            "ref", a["m_used"], a)
        if rerun is None:
            fail(f"{name}: row {i} m_used {a.get('m_used')} != {b.get('m_used')}")
        if (side, m) not in capped:
            capped[(side, m)] = rerun(side, m)
        slow = capped[(side, m)][i]
        over = (slow["delta"] - slow["threshold"]) / slow["threshold"]
        print(f"[{name}] row {i}: exit rungs {a['m_used']} vs {b['m_used']}; at rung "
              f"{m} the {side} path's delta {slow['delta']:.6g} is {over:+.4g} of its "
              f"threshold {slow['threshold']:.6g} (other path: delta "
              f"{fast['delta']:.6g}, threshold {fast['threshold']:.6g})", flush=True)
        if slow["m_used"] != m or not 0.0 < over <= FLIP_FRAC:
            fail(f"{name}: row {i} exits at rung {m} on one path only, and the "
                 f"other's delta is {over:+.4g} of the threshold (limit {FLIP_FRAC})")
        if side == "got":
            got[i] = slow
        else:
            ref[i] = slow
    worst = 0.0
    gap_thr = 0.0
    bitwise = True
    for a, b in zip(got, ref):
        sa, sb = np.asarray(a["token_scores"]), np.asarray(b["token_scores"])
        bitwise &= bool(np.array_equal(sa, sb)) and a["delta"] == b["delta"]
        scale = max(float(np.abs(sb).max()), 1e-30)
        err = float(np.abs(sa - sb).max()) / scale
        derr = abs(a["delta"] - b["delta"]) / max(abs(b["f_x"] - b["f_baseline"]), 1e-30)
        worst = max(worst, err, derr)
        if "threshold" in b:
            gap_thr = max(gap_thr, abs(a["delta"] - b["delta"]) / b["threshold"])
    print(f"[{name}] max_rel_err={worst:.3g} rtol={rtol} bitwise={bitwise}"
          + (f" max_delta_gap/threshold={gap_thr:.3g}" if "threshold" in ref[0] else ""),
          flush=True)
    if worst > rtol:
        fail(f"{name}: attributions differ by {worst:.3g} > {rtol}")


def phase_a(seed: int) -> None:
    from repro.launch.explain import load_workload, make_engine, serve_round

    args = launcher_args([
        "--workload", "vit", "--published", "--requests", "8",
        "--seed", str(seed), "--method", "ig", "--schedule", "paper",
        "--adaptive", "--tol", "1e-2", "--m", "16", "--m-max", "128",
        "--chunk", str(VIT_CHUNK),
    ])
    wl = load_workload(args)
    cfg = wl.cfg
    print(f"[A] {cfg.name}: {cfg.num_patches} tokens, d_model {cfg.d_model}, "
          f"{cfg.num_layers} layers, {cfg.num_heads} heads, {cfg.compute_dtype}",
          flush=True)
    reqs = wl.fixed_reqs
    ig_xla = serve_twice("A ig", make_engine(args, wl), reqs)
    serve_twice("A idgi", make_engine(args, wl, method="idgi", adaptive=False, m=32), reqs)
    serve_twice("A occlusion", make_engine(args, wl, method="occlusion", adaptive=False), reqs)
    kernels = dict(attn="flash", fused=True, use_kernels=True)
    eng = make_engine(args, wl, **kernels)
    ig_k = serve_twice("A ig kernels", eng, reqs)
    check_kernels_compiled(eng)

    def rerun(side: str, m: int) -> list[dict]:
        kw = kernels if side == "got" else {}
        return serve_round(make_engine(args, wl, m_max=m, **kw), None, reqs)

    compare("A kernels vs XLA", ig_k, ig_xla, KERNEL_RTOL, rerun)


def check_kernels_compiled(engine) -> None:
    """Every kernel-path executable runs its Pallas kernels compiled."""
    for key, (compiled, _) in engine._cache.items():
        if "tpu_custom_call" not in compiled.as_text():
            fail(f"A ig kernels: executable {key[:2]} has no tpu_custom_call")
    print(f"[A ig kernels] tpu_custom_call in all {len(engine._cache)} executables",
          flush=True)


def reference_ig(cfg, params, req, m: int, rule: str):
    """Plain IG of one request: a jax.grad of the target log-prob per node
    at batch 1 — no engine, bucket padding, scan, chunk or batching — at the
    engine's uniform nodes and pad-embedding baseline. The params are an
    argument (a closure would embed 3 GB of them in the program); remat
    only bounds its memory. Returns (token scores, f(x), f(x'))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.baselines import pad_embedding
    from repro.core.schedule import uniform
    from repro.models.registry import Model

    model = Model(cfg)
    x = model.embed_inputs(params, {"tokens": jnp.asarray(req.tokens)[None]})
    x0 = pad_embedding(params["embed"]["embedding"], x)
    aux = {"target": jnp.asarray([req.target], jnp.int32),
           "pos": jnp.asarray([len(req.tokens) - 1], jnp.int32)}

    def f(p, e):
        return model.target_logprob_at_fn(p, remat=True)(e, aux)[0]

    grad, fwd = jax.jit(jax.grad(f, argnums=1)), jax.jit(f)
    sched = uniform(m, rule)
    acc = jnp.zeros(x.shape, jnp.float32)
    for a, w in zip(np.asarray(sched.alphas), np.asarray(sched.weights)):
        acc = acc + float(w) * grad(params, x0 + float(a) * (x - x0)).astype(jnp.float32)
    scores = ((x - x0).astype(jnp.float32) * acc).sum(-1)[0]
    return np.asarray(scores), float(fwd(params, x)), float(fwd(params, x0))


def phase_b(seed: int) -> None:
    import dataclasses

    import numpy as np

    from repro.launch.explain import load_workload, make_engine, make_scheduler, make_traffic

    args = launcher_args([
        "--arch", "mamba2-780m", "--published", "--seed", str(seed),
        "--method", "ig", "--schedule", "paper", "--m", "16",
        "--chunk", str(MAMBA_CHUNK), "--scheduler",
    ])
    wl = load_workload(args)
    cfg = wl.cfg
    print(f"[B] {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"ssm_state {cfg.ssm_state}, vocab {cfg.vocab_size}, "
          f"{cfg.param_count() / 1e9:.3f}B params, {cfg.compute_dtype}", flush=True)
    reqs = make_traffic(cfg, 4, 129, 256, np.random.default_rng(seed))
    engine = make_engine(args, wl)
    out = serve_twice("B ig", engine, reqs, make_scheduler(args, engine))
    print("[B ig] per row (tokens, f_x, f_baseline, delta/|f_x - f_baseline|): "
          + "; ".join(f"{len(r.tokens)} {o['f_x']:.6g} {o['f_baseline']:.6g} "
                      f"{rel_delta(o):.6g}" for r, o in zip(reqs, out)), flush=True)

    # the gradient path at published widths against a plain reference: the
    # uniform schedule's nodes are a closed form, so both sides integrate
    # at the same points
    uni = make_engine(args, wl, schedule="uniform")
    got = serve_twice("B ig uniform", uni, reqs)[0]
    rule = uni._explainer.rule
    failed = []
    for tag, rcfg in (
        (cfg.compute_dtype, cfg),
        ("float32", dataclasses.replace(cfg, compute_dtype="float32")),
    ):
        t0 = time.perf_counter()
        scores, fx, fb = reference_ig(rcfg, wl.params, reqs[0], args.m, rule)
        sa = np.asarray(got["token_scores"])
        err = float(np.abs(sa - scores).max()) / max(float(np.abs(scores).max()), 1e-30)
        d_ref = abs(float(scores.sum()) - (fx - fb))
        derr = abs(got["delta"] - d_ref) / max(abs(fx - fb), 1e-30)
        print(f"[B ig uniform vs jax.grad {tag}] row 0 ({len(reqs[0].tokens)} tokens): "
              f"max_rel_err={err:.4g} (rtol {MAMBA_REF_RTOL}) delta_err={derr:.4g} "
              f"(limit {MAMBA_REF_DELTA}) f_x {got['f_x']:.6g} "
              f"vs {fx:.6g}, f_baseline {got['f_baseline']:.6g} vs {fb:.6g}, delta "
              f"{got['delta']:.6g} vs {d_ref:.6g} ({time.perf_counter() - t0:.1f}s)",
              flush=True)
        if not (err <= MAMBA_REF_RTOL and derr <= MAMBA_REF_DELTA):
            failed.append(f"{tag}: scores {err:.4g}, delta {derr:.4g}")
    if failed:
        fail(f"B ig uniform: engine vs plain jax.grad reference: {'; '.join(failed)}")


def phase_mesh(seed: int) -> None:
    import jax

    from repro.launch.explain import load_workload, make_engine, serve_round
    from repro.launch.mesh import make_explain_mesh

    args = launcher_args([
        "--workload", "vit", "--published", "--requests", "8",
        "--seed", str(seed), "--method", "ig", "--schedule", "paper",
        "--tol", "1e-2", "--m", "16", "--m-max", "128", "--chunk", str(VIT_CHUNK),
    ])
    wl = load_workload(args)
    mesh = make_explain_mesh(4, 1)
    for adaptive in (False, True):
        tag = "adaptive" if adaptive else "fixed-m"
        one = serve_twice(f"mesh {tag} 1-chip", make_engine(args, wl, adaptive=adaptive),
                          wl.fixed_reqs)
        eng = make_engine(args, wl, adaptive=adaptive, mesh=mesh)
        four = serve_twice(f"mesh {tag} 4-chip", eng, wl.fixed_reqs)
        for key, (compiled, shardings) in eng._cache.items():
            embeds = compiled.input_shardings[0][1]
            if shardings is None or len(embeds.device_set) != 4 or embeds.is_fully_replicated:
                fail(f"mesh {tag}: executable {key[:2]} is not sharded over 4 devices")
        print(f"[mesh {tag}] {len(eng._cache)} executables, batch sharded over "
              f"{len(jax.devices())} devices", flush=True)

        def rerun(side: str, m: int) -> list[dict]:
            e = make_engine(args, wl, adaptive=True, m_max=m,
                            mesh=mesh if side == "got" else None)
            return serve_round(e, None, wl.fixed_reqs)

        compare(f"mesh {tag} 4-chip vs 1-chip", four, one, MESH_RTOL, rerun)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and its one-chip comparison")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"JAX found no usable backend: {e}", file=sys.stderr)
        return 3
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX reports platform {dev.platform!r}", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX has {len(devices)}",
              file=sys.stderr)
        return 3

    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed)
    else:
        phase_a(args.seed)
        gc.collect()  # Phase A's engines and executables leave the device
        phase_b(args.seed)
    print(f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
