"""Roofline-driven per-bucket autotuner for the stage-2 hot path (§10).

The serving engine's stage-2 executables have three latency knobs that the
compiler cannot pick for us: the scan ``chunk`` (how many interpolation steps
fold into the batch axis per grad call — small chunks bound memory, large
chunks amortize dispatch) and the Pallas ``block_k``/``block_f`` tile sizes
(VMEM residency of the fused interp/accum kernels). The right values depend
on the bucket shape AND the device, so they are tuned per
``(bucket, device_kind)`` and persisted:

  1. every candidate is AOT-compiled and priced from
     ``compiled.cost_analysis()`` — bytes-accessed over HBM bandwidth and
     FLOPs over peak give the roofline bound (``repro.roofline.
     hotpath_terms``); candidates that the roofline already rules out are
     never measured;
  2. the surviving few run a short measured sweep (warmed wall-clock,
     median of ``rounds``); the winner is the measured-fastest;
  3. winners land in ``results/autotune_<device>.json`` keyed by
     ``bucket_key`` (bucket shape + accumulator class + schedule + m +
     n_int + fused), which ``ExplainEngine(autotune=True)`` loads at
     construction — steady-state serving then runs every bucket at its
     tuned config with zero extra compiles (the tuned chunk is part of the
     executable cache key, exactly like the untuned one).

The adaptive m-ladder is NOT tuned per bucket: escalation re-batches
survivors across bucket shapes mid-flight, and the §7 resume contract
requires one chunk along the whole ladder — a per-bucket chunk would change
the scan boundaries between rungs. Adaptive serving keeps the engine-wide
``chunk``; the tuned configs apply to the fixed-m path.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import numpy as np

from repro.roofline import cost_analysis_dict, hardware_for, hotpath_terms

DEFAULT_BLOCK_K = 8
DEFAULT_BLOCK_F = 512


@dataclass(frozen=True)
class HotpathConfig:
    """One tuned stage-2 configuration for a bucket.

    ``attn_block_q``/``attn_block_k`` are the flash-attention kernel tilings
    (0 = the model config's defaults); they only matter for engines serving
    an ``attn_impl == "flash"`` model, where the attention blocks are baked
    into the differentiated model function itself.
    """

    chunk: int
    block_k: int = DEFAULT_BLOCK_K
    block_f: int = DEFAULT_BLOCK_F
    attn_block_q: int = 0
    attn_block_k: int = 0


def device_kind() -> str:
    """Sanitized ``jax.Device.device_kind`` of device 0 (cache-file suffix)."""
    kind = jax.devices()[0].device_kind
    return re.sub(r"[^a-z0-9]+", "_", kind.lower()).strip("_")


def cache_path(results_dir: str = "results", device: Optional[str] = None) -> str:
    """``results/autotune_<device>.json`` — one cache file per device kind."""
    return os.path.join(results_dir, f"autotune_{device or device_kind()}.json")


def bucket_key(
    bucket: tuple[int, int],
    accum: str,
    schedule: str,
    m: int,
    n_int: int,
    fused: bool,
    attn: str = "auto",
) -> str:
    """Cache key for one bucket's tuned config (DESIGN.md §10).

    Keyed by everything that changes the compiled stage-2 program EXCEPT the
    knobs being tuned: the bucket shape, the accumulator CLASS (methods
    sharing an accumulator share executables, §8), the schedule family, the
    (m, n_int) budget, whether stage 2 is fused, and the model's attention
    implementation (``"+flash"`` suffix — a flash model compiles a different
    program than the materializing one, so their tuned configs never alias).
    The device rides the cache FILENAME (``cache_path``), not the key.
    """
    tag = "fused" if fused else "unfused"
    if attn != "auto":
        tag += f"+{attn}"
    return f"B{bucket[0]}xS{bucket[1]}/{accum}/{schedule}/m{m}/n{n_int}/{tag}"


@dataclass
class AutotuneCache:
    """On-disk ``bucket_key -> tuned config + measurements`` map."""

    device: str = ""
    entries: dict = field(default_factory=dict)

    @classmethod
    def load(cls, results_dir: str = "results", device: Optional[str] = None):
        """Load the device's cache; a missing file is an empty cache.

        NEVER raises on a bad file: a corrupted/truncated JSON payload, a
        non-dict payload, or a payload recorded for a DIFFERENT device kind
        (someone copied a results dir between machines — its tuned chunks
        would silently mis-tune this device) all warn and return an empty
        cache. A broken autotune file may cost re-tuning, never serving.
        """
        device = device or device_kind()
        path = cache_path(results_dir, device)
        if not os.path.exists(path):
            return cls(device=device)
        try:
            with open(path) as fh:
                payload = json.load(fh)
            if not isinstance(payload, dict) or not isinstance(
                payload.get("entries", {}), dict
            ):
                raise ValueError(f"malformed payload {type(payload).__name__}")
        except (json.JSONDecodeError, ValueError, OSError) as e:
            warnings.warn(
                f"AutotuneCache: unreadable cache at {path} ({e}); "
                "starting with an empty cache",
                stacklevel=2,
            )
            return cls(device=device)
        recorded = payload.get("device", device)
        if recorded != device:
            warnings.warn(
                f"AutotuneCache: {path} was tuned for device {recorded!r}, "
                f"not {device!r}; ignoring its entries",
                stacklevel=2,
            )
            return cls(device=device)
        return cls(device=device, entries=payload.get("entries", {}))

    def entries_fingerprint(self) -> str:
        """sha256 of the loaded entries — rides the result-cache key (a
        tuned chunk changes scan boundaries and therefore attribution bits;
        ``ExplainEngine.request_cache_key``)."""
        blob = json.dumps(self.entries, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()

    def save(self, results_dir: str = "results") -> str:
        os.makedirs(results_dir, exist_ok=True)
        path = cache_path(results_dir, self.device)
        with open(path, "w") as fh:
            json.dump({"device": self.device, "entries": self.entries}, fh, indent=1)
        return path

    def config_for(self, key: str) -> Optional[HotpathConfig]:
        e = self.entries.get(key)
        if e is None:
            return None
        return HotpathConfig(
            chunk=int(e["chunk"]),
            block_k=int(e.get("block_k", DEFAULT_BLOCK_K)),
            block_f=int(e.get("block_f", DEFAULT_BLOCK_F)),
            attn_block_q=int(e.get("attn_block_q", 0)),
            attn_block_k=int(e.get("attn_block_k", 0)),
        )

    def put(self, key: str, cfg: HotpathConfig, metrics: dict) -> None:
        self.entries[key] = {
            "chunk": cfg.chunk, "block_k": cfg.block_k, "block_f": cfg.block_f,
            "attn_block_q": cfg.attn_block_q, "attn_block_k": cfg.attn_block_k,
            **metrics,
        }


def chunk_candidates(m: int) -> list[int]:
    """Power-of-two divisors of ``m`` (ascending, ``m`` itself last).

        >>> chunk_candidates(8)
        [1, 2, 4, 8]
        >>> chunk_candidates(12)
        [1, 2, 4, 12]
    """
    out = [c for c in (2**i for i in range(m.bit_length())) if m % c == 0]
    if m not in out:
        out.append(m)
    return out


def _median_latency(call, args, rounds: int) -> float:
    call(args)  # warm (compile already done AOT; first call pays transfers)
    ts = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(call(args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def autotune_engine(
    engine,
    requests: Sequence,
    *,
    rounds: int = 3,
    max_measured: int = 3,
    block_k_grid: Sequence[int] = (DEFAULT_BLOCK_K,),
    block_f_grid: Sequence[int] = (DEFAULT_BLOCK_F,),
    attn_block_grid: Sequence[tuple[int, int]] = ((0, 0),),
    results_dir: str = "results",
    save: bool = True,
) -> dict:
    """Tune (chunk, block_k, block_f[, attn blocks]) per touched bucket.

    ``engine`` is an ``ExplainEngine``; ``requests`` is sample traffic whose
    plan buckets define what gets tuned (tune with the traffic you serve).
    Candidate configs are compiled standalone — the engine's executable
    cache and stats are untouched — priced by their roofline bound
    (``hotpath_terms`` under ``hardware_for(device_kind)``), and only the
    ``max_measured`` roofline-best run the measured sweep. Block grids
    beyond the defaults only matter when the engine injects Pallas kernels
    (``use_kernels=True``); the default single-point grids keep the sweep
    to a chunk scan. ``attn_block_grid`` sweeps (attn_block_q, attn_block_k)
    flash-attention tilings and only applies to flash engines ((0, 0) = the
    model config's blocks); it is ignored — one (0, 0) point — otherwise.

    Returns a report dict (per-bucket candidates + winners); with ``save``
    the winners are persisted to ``results/autotune_<device>.json`` for
    ``ExplainEngine(autotune=True)`` to load.
    """
    from repro.serve.batching import plan_buckets  # local: avoid import cycle

    hw = hardware_for(jax.devices()[0].device_kind)
    cache = AutotuneCache.load(results_dir)
    # mirror ExplainEngine.explain's plan exactly — path-ensemble methods
    # replicate requests n_samples× BEFORE bucketing, so the tuned bucket
    # shapes must come from the expanded traffic or the keys never match
    n = engine.n_samples
    expanded = (
        list(requests) if n == 1 else [r for r in requests for _ in range(n)]
    )
    plan = plan_buckets(
        expanded,
        seq_buckets=engine.seq_buckets,
        batch_buckets=engine.batch_buckets,
        max_batch=engine.max_batch,
        pad_id=engine.pad_id,
        batch_multiple=engine.dp,
    )
    attn_grid = (
        tuple(attn_block_grid)
        if getattr(engine, "attn", "auto") == "flash"
        else ((0, 0),)
    )
    report = {"device": cache.device, "hw": hw.name, "buckets": {}}
    seen: set[tuple[int, int]] = set()
    for bb in plan:
        if bb.bucket in seen:
            continue
        seen.add(bb.bucket)
        args = (engine.params, *engine._bucket_inputs(bb))
        sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        cands = []
        for chunk in chunk_candidates(engine.m):
            for bk in block_k_grid:
                for bf in block_f_grid:
                    for abq, abk in attn_grid:
                        cfg = HotpathConfig(chunk, bk, bf, abq, abk)
                        compiled, _, _ = engine._compile(engine._attr_fn_at(cfg), sds, ())
                        terms = hotpath_terms(cost_analysis_dict(compiled), hw)
                        cands.append({"cfg": cfg, "compiled": compiled, **terms})
        # roofline prune: only the predicted-fastest few get measured
        cands.sort(key=lambda c: c["bound_s"])
        for c in cands[:max_measured]:
            c["latency_s"] = _median_latency(
                lambda a, ex=c["compiled"]: ex(*a), args, rounds
            )
        best = min(cands[:max_measured], key=lambda c: c["latency_s"])
        key = bucket_key(
            bb.bucket, engine._spec.accum, engine.schedule, engine.m,
            engine.n_int, engine.fused, attn=getattr(engine, "attn", "auto"),
        )
        cache.put(
            key,
            best["cfg"],
            {
                "bytes_accessed": best["bytes_accessed"],
                "latency_s": best["latency_s"],
                "bound_s": best["bound_s"],
                "dominant": best["dominant"],
            },
        )
        report["buckets"][key] = {
            "winner": vars(best["cfg"]) | {"latency_s": best["latency_s"]},
            "candidates": [
                {
                    **vars(c["cfg"]),
                    "bytes_accessed": c["bytes_accessed"],
                    "bound_s": c["bound_s"],
                    "latency_s": c.get("latency_s"),
                }
                for c in cands
            ],
        }
    if save:
        report["path"] = cache.save(results_dir)
    return report
