"""ExplainEngine — shape-bucketed NUIG serving with a compiled-executable cache.

The paper's 2.6–3.6× latency win assumes the two-stage pipeline runs as ONE
hot compiled program. This engine makes that true under real traffic:

  * heterogeneous ``ExplainRequest``s are padded into shape buckets
    (``repro.serve.batching``: powers-of-two S, configurable ladder, plus a
    batch-axis ladder so (B, S) is a small closed set);
  * padded positions are masked out of the stage-1 probe and the stage-2
    attribution/δ (see ``repro.core.ig.attribute``'s ``mask``) — they receive
    exactly zero attribution and δ is over real tokens only;
  * one executable per ``(bucket_shape, accumulator, schedule, m, n_int,
    chunk)`` key is AOT-compiled (``jit(...).lower(...).compile()``) and
    cached, so steady-state traffic never recompiles — the cache and its
    hit/miss/latency stats are first-class, inspectable state;
  * the model parameters are the first ARGUMENT of every executable, never
    a closed-over constant: a closure would embed the weights in the
    program (3 GB of HLO constants for a 780M-parameter model), so each
    program function takes ``params`` and rebuilds the model function over
    it while tracing (``_f_for``);
  * every schedule family in ``repro.core.schedule.SCHEDULES`` rides the same
    compiled path (the registry's uniform builder signature), and so does
    every attribution method in ``repro.core.methods.METHODS`` (DESIGN.md §8):
    executables are keyed by the method's accumulator CLASS (``spec.accum``),
    so ``ig``/``noise_tunnel``/``expected_grad`` share one warmed riemann set
    and ``idgi`` compiles its own — either way the shape set stays closed.
    Path-ensemble methods are served by replicating each request
    ``n_samples``× at plan time and perturbing rows in embedding space in
    the bucket's prep program (outside the attribution executables), then
    averaging each request's contiguous sample results;
  * an optional device mesh shards the folded (batch × step) stage-2 axis
    via the pjit specs in ``repro.sharding`` (DESIGN.md §9): every bucket /
    start / hop executable is compiled with ``NamedSharding``s resolved per
    argument tree (``explain_arg_shardings``), cache keys carry the mesh axis
    sizes (``mesh_cache_key``) so single-device and sharded entries coexist,
    and bucket batches are padded up to a multiple of the data-parallel
    extent (``dp_size``) at plan time so the shardings always apply. δ and
    the adaptive escalation decisions are computed from device-local per-row
    reductions (feature axes stay replicated), so a sharded engine escalates
    bit-identically to the unsharded one. A bucket that somehow reaches the
    compile step without a dp-divisible batch serves replicated and is
    counted in ``EngineStats.mesh_fallbacks`` — never silently.

**Hot-path bandwidth** (DESIGN.md §10): with ``fused=True`` stage 2 composes
interpolation with the model forward under one VJP
(``ig.attribute(fused=True)``), so the (B·chunk, *F) interpolant batch never
crosses a program boundary and riemann-class methods collapse the per-step
gradient batch into one (B, *F) cotangent. Hop executables donate their
``IGState`` (ladder escalation reuses the f32 accumulator buffer in place),
``autotune=True`` loads per-(bucket, device) tuned (chunk, block_k, block_f)
configs from ``serve.autotune``'s on-disk cache, ``use_kernels=True``
injects the Pallas kernel set at those block sizes, and every compile
records its ``cost_analysis`` bytes-accessed / peak-bytes budget on the
bucket's stats row.

**Adaptive iso-convergence** (``adaptive=True``, DESIGN.md §7): ``m`` becomes
the base rung of a pow-2 m-ladder instead of a fixed budget. Each bucket runs
rung 0 (probe + base schedule + resumable accumulation), then examples whose
completeness gap δ still exceeds ``tol · |f(x) − f(x′)|`` are re-batched
together and escalated: their schedules are refined (nested doubling — prior
gradients are never discarded, see ``schedule.refine_nested``) and only the
NEW nodes run, through "hop" executables keyed on ``(bucket, n_new, chunk)``
— method-independent, because schedules are data. Ladder hops therefore only
ever touch the same closed set of warmed shapes as fixed-m serving: zero
recompiles at steady state, per-request shapes never exist.

``ExplainService`` remains as a thin compatibility shim over this engine.
"""
from __future__ import annotations

import functools
import hashlib
import warnings
import dataclasses
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import ig, methods as methods_mod, perturb
from repro.core.api import Explainer
from repro.core.baselines import pad_embedding
from repro.core.fingerprint import model_fingerprint
from repro.core.probes import probe_cost
from repro.core.schedule import Schedule, family, m_ladder
from repro.models.registry import model_for
from repro.roofline import cost_analysis_dict
from repro.serve.autotune import AutotuneCache, HotpathConfig, bucket_key
from repro.serve.result_cache import ResultCache
from repro.serve.spans import (
    CALL,
    COMPILE,
    GATHER,
    INPUTS,
    READBACK,
    REFINE,
    WAIT,
    span,
)
from repro.sharding import (
    DEFAULT_RULES,
    MeshRules,
    dp_size,
    explain_arg_shardings,
    mesh_cache_key,
)
from repro.serve.batching import (
    DEFAULT_BATCH_BUCKETS,
    DEFAULT_SEQ_BUCKETS,
    BucketBatch,
    pad_rows,
    plan_buckets,
)


@dataclass(frozen=True)
class ExplainRequest:
    tokens: np.ndarray  # (S,) int32 prompt — lengths may differ per request
    target: int  # token id whose next-token log-prob is attributed
    # feature-space request (patch models): (S, *F) float patch features from
    # ``models.vit.patchify``; ``tokens`` then only sets the length/bucket
    # (use e.g. arange(num_patches)) and ``target`` is the attributed class
    features: Optional[np.ndarray] = None
    # known endpoint value f(x) donated by the decode path (the probe-reuse
    # contract, docs/serving.md): the unified scheduler sets this to the
    # prefill forward's target log-prob, so the engine skips the α=1 probe
    # forward and the endpoint forward. Bit-identical to a self-computed
    # endpoint at float32 compute; dropped automatically for path-ensemble
    # methods (samples perturb x, so the donated value is for the wrong
    # point). None = the engine computes f(x) itself (the classic path).
    f_x: Optional[float] = None


@dataclass
class BucketStats:
    compiles: int = 0
    calls: int = 0
    requests: int = 0
    compile_s: float = 0.0
    total_s: float = 0.0  # wall time of cached calls (excludes compiles)
    # roofline-facing compile-time budgets (DESIGN.md §10): HBM traffic and
    # peak live bytes of the LAST executable compiled at this bucket shape,
    # from compiled.cost_analysis()/memory_analysis() — what the autotuner
    # ranks candidate configs by, surfaced per bucket so regressions are
    # observable in serving stats, not just in benchmarks
    bytes_accessed: float = 0.0
    peak_bytes: float = 0.0
    # the last executable at this shape recomputes each layer in its
    # backward pass: it did not fit device memory otherwise (``_compile``)
    remat: bool = False

    @property
    def mean_latency_s(self) -> float:
        return self.total_s / self.calls if self.calls else 0.0


@dataclass
class AdaptiveStats:
    """Aggregate δ-feedback serving counters (per-request values ride on the
    result dicts: ``m_used``, ``delta``, ``hops``, ``converged``). For
    path-ensemble methods every counter is per served ROW (sample), i.e.
    ``n_samples``× the user-visible request count."""

    requests: int = 0  # requests served adaptively
    converged: int = 0  # requests that reached δ ≤ tol·|f_x − f_b|
    early_exits: int = 0  # requests that converged below the ladder top
    hop_calls: int = 0  # escalation batches launched
    total_steps: int = 0  # Σ per-request m_used (iso-convergence metric)
    launched_steps: int = 0  # actual grad steps incl. batch-pad rows
    probe_forwards: int = 0  # stage-1 forwards (not gradient steps)
    m_used: dict = field(default_factory=dict)  # final rung -> request count

    @property
    def mean_m_used(self) -> float:
        return self.total_steps / self.requests if self.requests else 0.0


@dataclass
class EngineStats:
    hits: int = 0  # executable-cache hits
    misses: int = 0  # executable-cache misses == compilations
    buckets: dict = field(default_factory=dict)  # (B, S) -> BucketStats
    # hop executables get their own table: a hop at a plan-bucket shape does
    # different work per call (n_new new nodes, no probe/endpoints), so
    # folding it into `buckets` would corrupt per-bucket serving latency
    hop_buckets: dict = field(default_factory=dict)  # (B, S) -> BucketStats
    adaptive: AdaptiveStats = field(default_factory=AdaptiveStats)
    # buckets compiled WITHOUT shardings despite a multi-device mesh — the
    # mesh-divisible-padding contract (DESIGN.md §9) makes this unreachable
    # on the serving path; a nonzero count means padding was bypassed and
    # those buckets ran replicated (correct, but not scaled)
    mesh_fallbacks: int = 0
    # unified-scheduler counters (serve.scheduler): requests served a
    # fallback result after fault-policy exhaustion; decode work items run
    # ahead of queued explain hops (δ-aware preemption); and the scheduler
    # queue depth observed at the most recent dispatch
    degraded: int = 0
    preempted: int = 0
    queue_depth: int = 0
    # content-addressed RESULT cache (serve.result_cache) — a second cache
    # with its own counters: `hits`/`misses` above are the EXECUTABLE cache
    # (compile avoidance); these are whole-attribution replays (compute
    # avoidance). Mirrored from the ResultCache so one stats object reports
    # both in launch/explain and launch/serve
    result_hits: int = 0
    result_misses: int = 0
    result_evictions: int = 0
    result_bytes: int = 0
    # span name -> (count, seconds), each span's wall time including the
    # spans nested inside it (serve.spans; docs/serving.md)
    spans: dict = field(default_factory=dict)
    # bucket prep programs compiled (``ExplainEngine._bucket_inputs``): one
    # per argument shape, flat at steady state; not executable-cache misses
    # and not in any bucket row, so ``compiles``/``compile_s`` stay the
    # executables'; the ``repro.engine.compile`` span counts both
    prep_compiles: int = 0
    prep_compile_s: float = 0.0

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    @property
    def result_hit_rate(self) -> float:
        n = self.result_hits + self.result_misses
        return self.result_hits / n if n else 0.0

    def bucket(self, shape: tuple[int, int]) -> BucketStats:
        return self.buckets.setdefault(shape, BucketStats())

    def hop_bucket(self, shape: tuple[int, int]) -> BucketStats:
        return self.hop_buckets.setdefault(shape, BucketStats())

    @property
    def compiles(self) -> int:
        return sum(
            b.compiles for d in (self.buckets, self.hop_buckets) for b in d.values()
        )


def _peak_bytes(compiled: Any) -> Optional[float]:
    """Argument + output + temp bytes of one compiled program
    (``memory_analysis()``), or None where the backend reports none."""
    try:
        ma = compiled.memory_analysis()
        return float(
            ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
        )
    except Exception:  # noqa: BLE001 — backend-optional introspection
        return None


class ExplainEngine:
    """Bucketed, cache-compiled NUIG serving over one model + param set.

    Args (the load-bearing subset — see the module docstring for the design):
        cfg / params: an ``ArchConfig`` and its parameter pytree.
        method / schedule: names in ``methods.METHODS`` / ``schedule.SCHEDULES``.
        m, n_int, chunk: the stage-2 budget, stage-1 intervals, scan chunk.
        seq_buckets / batch_buckets: the (S, B) padding ladders.
        mesh / mesh_rules: optional ``jax.sharding.Mesh`` — shards the folded
            (batch × step) stage-2 axis across the mesh's data axes
            (DESIGN.md §9).
        adaptive / tol / m_max: δ-feedback serving up the pow-2 m-ladder.
        fused: fused stage 2 (DESIGN.md §10); the default False is the
            materializing oracle path (the BENCH_hotpath reference).
        use_kernels / autotune / autotune_dir: Pallas kernel injection and
            the per-(bucket, device) tuned-config cache (§10).
        attn: "flash" serves the model with ``attn_impl="flash"`` — every
            executable differentiates through the Pallas flash-attention
            custom VJP (docs/attention.md); tuned attention block sizes from
            the autotune cache rebuild the model closure per bucket.

    Example (tiny CPU-reduced LM, one mixed-length round):

        >>> import numpy as np, jax
        >>> from repro.configs import ARCHS, reduced
        >>> from repro.models.registry import Model
        >>> cfg = reduced(ARCHS["llama3-8b"])
        >>> params = Model(cfg).init(jax.random.PRNGKey(0))
        >>> eng = ExplainEngine(cfg, params, m=4, n_int=2, seq_buckets=(8,))
        >>> reqs = [ExplainRequest(np.arange(1, 6, dtype=np.int32), target=7)]
        >>> out = eng.explain(reqs)
        >>> out[0]["token_scores"].shape, eng.stats.misses
        ((5,), 1)
        >>> _ = eng.explain(reqs)  # same bucket -> pure cache hit
        >>> eng.stats.misses, eng.stats.hits
        (1, 1)
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        *,
        method: str = "ig",
        schedule: str = "paper",
        m: int = 64,
        n_int: int = 4,
        chunk: int = 0,
        refine_rounds: int = 4,
        power: float = 0.5,
        pad_id: int = 0,
        seq_buckets: Sequence[int] = DEFAULT_SEQ_BUCKETS,
        batch_buckets: Optional[Sequence[int]] = DEFAULT_BATCH_BUCKETS,
        max_batch: int = 0,
        mesh: Optional[jax.sharding.Mesh] = None,
        mesh_rules: MeshRules = DEFAULT_RULES,
        adaptive: bool = False,
        tol: float = 1e-2,
        m_max: int = 0,
        n_samples: int = 0,
        sigma: float = 0.0,
        n_masks: int = 0,
        sample_seed: int = 0,
        fused: bool = False,
        use_kernels: bool = False,
        attn: str = "auto",
        autotune: bool = False,
        autotune_dir: str = "results",
        result_cache: Union[None, int, ResultCache] = None,
        hop_zero: bool = False,
        hop_zero_q: float = 0.75,
        hop_zero_min: int = 8,
    ):
        # attention implementation of the SERVED model: "flash" rebuilds the
        # config with attn_impl="flash" so every executable differentiates
        # through the Pallas custom-VJP kernel instead of materializing the
        # (B·K, H, S, S) score tensor; "auto" leaves the config untouched.
        # Rides every cache key — flash and materializing programs coexist.
        assert attn in ("auto", "flash"), attn
        if attn == "flash" or getattr(cfg, "attn_impl", "auto") == "flash":
            self.attn = "flash"
            cfg = dataclasses.replace(cfg, attn_impl="flash")
        else:
            self.attn = "auto"
        self.cfg = cfg
        self.params = params
        self.method = method
        self.schedule = schedule
        self._spec = methods_mod.get(method)
        self.m = m
        self.n_int = n_int
        self.chunk = chunk
        self.pad_id = pad_id
        # fused stage 2 (DESIGN.md §10): bandwidth-optimal, opt-in — fused
        # and unfused agree to float tolerance but not bitwise, and under
        # bf16 the w-seeded backward rounds cotangents at a different scale
        # (≲0.5% relative), so flipping the serving default is gated on the
        # BENCH_hotpath trace/bytes/latency evidence, not assumed
        self.fused = fused
        self.use_kernels = use_kernels
        # read by ``_f_for`` while tracing: True while ``_compile`` re-traces
        # a program that did not fit device memory with each layer recomputed
        # in the backward pass
        self._remat = False
        # per-(bucket, device) tuned (chunk, block_k, block_f) configs from
        # serve.autotune — loaded once at construction; a missing cache file
        # is an empty cache (every bucket falls back to the engine-wide
        # chunk and the default Pallas blocks)
        self._autotune_cache = (
            AutotuneCache.load(autotune_dir) if autotune else None
        )
        self.seq_buckets = tuple(seq_buckets)
        self.batch_buckets = tuple(batch_buckets) if batch_buckets else None
        self.max_batch = max_batch
        # forward-only perturbation class (DESIGN.md §8 / core.perturb): no
        # VJP exists, so δ carries no convergence meaning — the adaptive
        # m-ladder is a gradient-class contract and must be refused loudly
        if self._spec.forward_only and adaptive:
            raise ValueError(
                f"method {self._spec.name!r} is forward-only; the δ-adaptive "
                "m-ladder needs the gradient class (serve it fixed-budget)"
            )
        # mask budget P — the forward analogue of m (n_masks=0: spec default)
        self.n_masks = n_masks if n_masks else (self._spec.n_masks or 64)
        self.mesh = mesh
        self.mesh_rules = mesh_rules
        # data-parallel extent: every bucket batch is padded to a multiple of
        # this at plan time (mesh-divisible padding, DESIGN.md §9)
        self.dp = dp_size(mesh, mesh_rules)
        # cache keys carry the mesh axis sizes so single-device and sharded
        # executables coexist in one cache
        self._mesh_key = mesh_cache_key(mesh)
        self.adaptive = adaptive
        self.tol = tol
        self.m_max = m_max if m_max else (8 * m if adaptive else m)
        self.m_ladder = m_ladder(m, self.m_max)
        # path-ensemble serving: each request becomes n_samples plan rows
        self.n_samples = (
            (n_samples if n_samples else self._spec.n_samples)
            if self._spec.expand is not None
            else 1
        )
        self.sigma = sigma if sigma else self._spec.sigma_default
        self.sample_seed = sample_seed
        self.model = model_for(cfg)
        self.stats = EngineStats()
        self._cache: dict[tuple, Any] = {}  # key -> compiled executable
        # ("prep", argument shapes) -> compiled bucket prep program
        # (_bucket_inputs). The key holds shapes alone: ``_prep`` closes over
        # ``_spec``, ``sigma``, ``sample_seed``, ``n_masks`` and ``pad_id``,
        # which must stay fixed after __init__. serve.warm_state saves and
        # restores these beside the executables.
        self._prep_cache: dict[tuple, Any] = {}
        # content-addressed attribution cache (serve.result_cache): an int
        # is a byte budget, a ResultCache instance is shared/injected, None
        # (default) disables — repeat requests then always recompute
        if isinstance(result_cache, ResultCache):
            self.result_cache: Optional[ResultCache] = result_cache
        elif result_cache:
            self.result_cache = (
                ResultCache()  # True -> the default byte budget
                if result_cache is True
                else ResultCache(max_bytes=int(result_cache))
            )
        else:
            self.result_cache = None
        # hop-zero starting rung (DESIGN.md §7 amortization): pick the
        # adaptive ladder's starting m from the per-(S-bucket, method)
        # m_used-history quantile — repeat-heavy traffic skips the rungs it
        # historically escalated through. History only accumulates from
        # base-rung runs (no ratcheting) and never-seen buckets keep the
        # base rung, so their traces are unchanged.
        self.hop_zero = hop_zero and adaptive
        self.hop_zero_q = hop_zero_q
        self.hop_zero_min = hop_zero_min
        self._delta_hist: dict[tuple[int, str], list[int]] = {}
        # per-rung Explainer variants for hop-zero starts (m0 != m)
        self._explainers_m: dict[int, Explainer] = {}
        # (fn, arg ShapeDtypeStructs, donate_argnums) per compiled key —
        # what warm-start persistence needs to jax.export the set; the prep
        # programs' in a dict of their own
        self._export_info: dict[tuple, tuple] = {}
        self._prep_export_info: dict[tuple, tuple] = {}
        self._model_fp: Optional[str] = None
        # params replicated over the mesh, placed on first sharded call
        self._mesh_params: Any = None
        # the compiled per-row unit: expansion stripped (row_spec) — the
        # engine samples the ensemble itself at batch-construction time
        self._explainer = Explainer(
            self.model.target_logprob_at_fn(params),
            method=self._spec.row_spec(),
            schedule=schedule,
            m=m,
            n_int=n_int,
            chunk=chunk,
            refine_rounds=refine_rounds,
            power=power,
            fused=fused,
            **self._kernel_kwargs(HotpathConfig(chunk)),
        )

    # -- compiled-executable cache ----------------------------------------

    def _kernel_kwargs(self, cfg: HotpathConfig) -> dict:
        """Pallas injection kwargs for one tuned config (``use_kernels``).

        Fused mode injects the custom-VJP interp-plus-carry op (its backward
        is the fused accumulation kernel, DESIGN.md §10) plus the class
        accumulator for quadratic methods; unfused mode injects the classic
        interpolate + accumulate pair. Forward-only methods have no gradient
        accumulator — their kernel injection is the lstsq solve hook inside
        ``_fwd_fn_at``."""
        if not self.use_kernels or self._spec.forward_only:
            return {}
        from repro.kernels.ig_accum.ops import accum_fn_for
        from repro.kernels.interp_accum.ops import interp_accum
        from repro.kernels.interpolate.ops import interpolate as interpolate_op

        blocks = {"block_k": cfg.block_k, "block_f": cfg.block_f}
        kw = {"accum_fn": functools.partial(accum_fn_for(self._spec.accum), **blocks)}
        if self.fused:
            kw["interp_add_fn"] = functools.partial(interp_accum, **blocks)
        else:
            kw["interp_fn"] = functools.partial(interpolate_op, **blocks)
        return kw

    def _cfg_for(self, bucket: tuple[int, int]) -> HotpathConfig:
        """The bucket's tuned (chunk, block_k, block_f), or the engine-wide
        defaults when no autotune entry exists (DESIGN.md §10)."""
        if self._autotune_cache is not None:
            tuned = self._autotune_cache.config_for(
                bucket_key(bucket, self._spec.accum, self.schedule, self.m,
                           self.n_int, self.fused, attn=self.attn)
            )
            if tuned is not None:
                return tuned
        return HotpathConfig(self.chunk)

    def _f_for(self, cfg: HotpathConfig, params: Any):
        """The model function over ``params`` at one tuned config's
        attention block sizes.

        Called while tracing, with ``params`` the program's first argument.
        Flash models bake (attn_block_q, attn_block_k) into the
        differentiated function itself; (0, 0) and non-flash engines keep
        the config's blocks.
        """
        mcfg = self.cfg
        if self.attn == "flash" and (cfg.attn_block_q, cfg.attn_block_k) != (0, 0):
            mcfg = dataclasses.replace(
                mcfg, attn_block_q=cfg.attn_block_q, attn_block_k=cfg.attn_block_k
            )
        return model_for(mcfg).target_logprob_at_fn(params, remat=self._remat)

    def _attr_fn_at(self, cfg: HotpathConfig):
        """Fixed-m bucket unit at one tuned config (also the autotuner's
        candidate-compile hook). Traced with a trailing (B,) ``f_x`` it is
        the probe-reuse variant that donates the known endpoint f(x) — a
        DIFFERENT program (one fewer probe forward, a B-row endpoint batch),
        so it gets its own cache-key flag (``_key``'s ``with_fx``)."""
        exp = replace(self._explainer, chunk=cfg.chunk, **self._kernel_kwargs(cfg))

        def attr_fn(params, embeds, baseline, aux, mask, f_x=None):
            return replace(exp, f=self._f_for(cfg, params)).attribute(
                embeds, baseline, aux, mask=mask, f_x=f_x
            )

        return attr_fn

    def _key(self, bucket: tuple[int, int], *, with_fx: bool = False) -> tuple:
        # keyed by accumulator CLASS, not method name: methods sharing an
        # accumulator share the warmed executables (DESIGN.md §8); the mesh
        # axis sizes ride every key so sharded and single-device entries
        # coexist (DESIGN.md §9); the resolved per-bucket HotpathConfig and
        # the fused/use_kernels program choices ride it too (§10), so tuned
        # and untuned entries never alias; ``with_fx`` separates probe-reuse
        # programs (docs/serving.md) from self-probing ones
        return (bucket, self._spec.accum, self.schedule, self.m, self.n_int,
                self._cfg_for(bucket), self.fused, self.use_kernels,
                self.attn, self._mesh_key, with_fx)

    # -- content-addressed identity (result cache + warm start) ------------

    @property
    def model_fingerprint(self) -> str:
        """sha256 of (config repr, params bytes) — computed once, lazily
        (hashing every param leaf is cheap on reduced models but real
        weights should pay it a single time)."""
        if self._model_fp is None:
            self._model_fp = model_fingerprint(self.cfg, self.params)
        return self._model_fp

    def _context_parts(self) -> list:
        """Everything engine-level that changes produced attribution BYTES.

        Keyed by METHOD NAME, not the accumulator class executables share:
        IDGI and IG attributions of one input are different artifacts. The
        bucket ladders are absent on purpose — the padding-invariance
        contract makes results independent of which bucket/batch a request
        lands in (tests/test_explain_engine.py exercises it)."""
        return [
            "ctx-v1", self.model_fingerprint, self.method, self.schedule,
            self.m, self.n_int, self.chunk, self.adaptive, self.tol,
            self.m_max, self.n_samples, self.sigma, self.sample_seed,
            self.n_masks, self.fused, self.use_kernels, self.attn,
            self._mesh_key, self.pad_id, self._autotune_cache is not None,
        ]

    def warm_context(self) -> str:
        """Identity a persisted warm state must match (serve.warm_state).

        Excludes the autotune ENTRIES fingerprint: the warm state carries
        the entries itself and installs them before any executable is
        consulted, so a restarted engine whose autotune file is gone can
        still restore."""
        return hashlib.sha256(repr(self._context_parts()).encode()).hexdigest()

    def request_cache_key(self, req: ExplainRequest) -> str:
        """sha256 content key for one request's attribution result.

        Engine context (including the loaded autotune entries — a tuned
        chunk changes scan boundaries and therefore bits) + the request's
        own bytes. The donated ``f_x`` rides the key conservatively — it is
        a program input — but is dropped exactly where ``explain()`` strips
        it (ensemble and forward-only methods), so donating and
        self-probing variants of those methods share entries."""
        parts = self._context_parts()
        if self._autotune_cache is not None:
            parts.append(self._autotune_cache.entries_fingerprint())
        h = hashlib.sha256(repr(parts).encode())
        tok = np.ascontiguousarray(np.asarray(req.tokens, np.int32))
        h.update(str(tok.shape).encode())
        h.update(tok.tobytes())
        h.update(str(int(req.target)).encode())
        if req.features is not None:
            f = np.ascontiguousarray(np.asarray(req.features, np.float32))
            h.update(b"feat")
            h.update(str(f.shape).encode())
            h.update(f.tobytes())
        f_x = req.f_x
        if self._spec.forward_only or self.n_samples > 1:
            f_x = None
        h.update(
            b"fx" + (np.float32(f_x).tobytes() if f_x is not None else b"none")
        )
        return h.hexdigest()

    def _sync_result_stats(self) -> None:
        """Mirror the ResultCache counters onto EngineStats (satellite 1)."""
        rc = self.result_cache
        if rc is not None:
            st = self.stats
            st.result_hits = rc.hits
            st.result_misses = rc.misses
            st.result_evictions = rc.evictions
            st.result_bytes = rc.bytes

    # -- hop-zero starting rung (DESIGN.md §7 amortization) ----------------

    def _explainer_for_m(self, m0: int) -> Explainer:
        """The per-row Explainer at ladder rung ``m0`` (hop-zero starts).

        ``m0 == m`` is the construction-time instance; higher rungs get a
        cached variant. The engine chunk divides m, m0 is a pow-2 multiple
        of m, so the §7 one-chunk-per-ladder contract holds unchanged."""
        if m0 == self.m:
            return self._explainer
        if m0 not in self._explainers_m:
            self._explainers_m[m0] = replace(self._explainer, m=m0)
        return self._explainers_m[m0]

    def _start_fn_for(self, m0: int):
        """Adaptive rung 0 at starting rung ``m0``: fused probe + base
        schedule + resumable stage 2.

        Returns the materialized per-example schedule too — the host needs it
        to refine on escalation (uniform's shared (m,) schedule is broadcast
        so survivor rows can be gathered independently). The optional
        trailing ``f_x`` is the probe-reuse variant (only ever compiled with
        it present or absent — the two signatures never alias, see
        ``_key``'s with_fx flag); the returned IGState carries the endpoints
        either way, so hop executables are IDENTICAL for both."""
        exp = self._explainer_for_m(m0)
        base = HotpathConfig(self.chunk)

        def start_fn(params, embeds, baseline, aux, mask, f_x=None):
            res, state, sched = replace(exp, f=self._f_for(base, params)).start(
                embeds, baseline, aux, mask=mask, f_x=f_x
            )
            B = embeds.shape[0]
            sched = Schedule(
                jnp.broadcast_to(sched.alphas, (B, sched.alphas.shape[-1])),
                jnp.broadcast_to(sched.weights, (B, sched.weights.shape[-1])),
            )
            return res, state, sched

        return start_fn

    def _hop_fn_for(self, m0: int):
        """One ladder hop: stage 2 over the refined schedule's new nodes only
        (method-independent — the schedule arrives as runtime data)."""
        exp = self._explainer_for_m(m0)
        base = HotpathConfig(self.chunk)

        def hop_fn(params, embeds, baseline, aux, mask, new_nodes, state):
            return replace(exp, f=self._f_for(base, params)).resume(
                embeds, baseline, aux, new_nodes, state, mask=mask
            )

        return hop_fn

    def _hop_zero_m(self, bucket: tuple[int, int]) -> int:
        """The adaptive ladder's starting rung for one bucket.

        With enough recorded base-rung history for (S-bucket, method), the
        smallest ladder rung covering the ``hop_zero_q`` quantile of final
        ``m_used`` — repeat-heavy traffic starts where it historically
        ended. Below ``hop_zero_min`` observations (and always for
        never-seen buckets) the base rung ``m`` is returned, so such
        traffic's m_used/δ traces are EXACTLY the non-hop-zero ones."""
        if not self.hop_zero:
            return self.m
        hist = self._delta_hist.get((bucket[1], self.method))
        if not hist or len(hist) < self.hop_zero_min:
            return self.m
        q = float(np.quantile(np.asarray(hist, np.float64), self.hop_zero_q))
        for rung in self.m_ladder:
            if rung >= q:
                return rung
        return self.m_ladder[-1]

    def _record_m_used(self, seq_bucket: int, values: Sequence[int]) -> None:
        """Accumulate base-rung-start ``m_used`` outcomes (the hop-zero
        evidence; capped so a long-lived engine's history stays bounded)."""
        hist = self._delta_hist.setdefault((seq_bucket, self.method), [])
        hist.extend(int(v) for v in values)
        if len(hist) > 512:
            del hist[:-512]

    def _executable(
        self, key: tuple, bs: BucketStats, fn, args: tuple, donate: tuple = ()
    ) -> Any:
        """AOT-compiled program (+ its input shardings) for one cache key.

        ``bs`` is the stats row (plan bucket or hop bucket) that the compile
        time is charged to. Under a mesh, input ``NamedSharding``s are
        resolved per argument tree (``explain_arg_shardings`` — hop args
        carry Schedule/IGState leaves beyond the 4-arg fixed-m tuple, all
        handled by the same leading-dim rule) and baked into the executable;
        mesh-divisible padding (DESIGN.md §9) guarantees they resolve, and a
        bucket that reaches here indivisible anyway compiles replicated and
        bumps ``EngineStats.mesh_fallbacks``. Returns ``(compiled,
        shardings)`` — callers feed the pair to ``_timed_call`` so inputs are
        placed onto the mesh before the call.

        ``donate`` (``donate_argnums``, counted over ``args``) marks args
        whose buffers the executable may overwrite — hop executables donate
        their ``IGState`` so ladder escalation reuses the (B, *F) f32
        accumulator in place instead of copying it each rung (DESIGN.md §10;
        every donated arg is constructed fresh per call, never read back
        after). ``fn`` takes ``params`` before ``args``; the compiled program
        does too (replicated under a mesh). Compile-time roofline budgets
        (bytes accessed, peak bytes) are recorded on ``bs``.
        """
        hit = key in self._cache
        if hit:
            self.stats.hits += 1
            return self._cache[key]
        self.stats.misses += 1
        bs.compiles += 1
        jit_kw = {}
        shardings = None
        if self.mesh is not None and self.dp > 1:
            shardings = explain_arg_shardings(self.mesh, args, self.mesh_rules)
            if shardings is not None:
                jit_kw["in_shardings"] = (self._replicated(), *shardings)
            else:
                self.stats.mesh_fallbacks += 1
                warnings.warn(
                    f"ExplainEngine: bucket batch {args[0].shape[0]} does not "
                    f"divide dp={self.dp}; serving replicated (key={key[:2]})",
                    stacklevel=2,
                )
        sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (self.params, *args)
        )
        donate = tuple(i + 1 for i in donate)  # params ride first
        with span(self.stats, COMPILE) as sp:
            compiled, fn, bs.remat = self._compile(fn, sds, donate, jit_kw, sp=sp)
            sp.tag(remat=bs.remat)
        bs.compile_s += sp.seconds
        bs.bytes_accessed = float(
            cost_analysis_dict(compiled).get("bytes accessed", 0.0)
        )
        peak = _peak_bytes(compiled)
        if peak is not None:
            bs.peak_bytes = peak
        self._cache[key] = (compiled, shardings)
        # what serve.warm_state needs to serialize this entry portably
        self._export_info[key] = (fn, sds, donate)
        return self._cache[key]

    def _compile(
        self, fn, sds: tuple, donate: tuple, jit_kw: Optional[dict] = None,
        *, sp: Optional[span] = None,
    ) -> tuple[Any, Any, bool]:
        """Compile ``fn`` at ``sds``; a program that does not fit device
        memory is compiled again with each layer recomputed in its backward.

        Whether a stage-2 program needs remat depends on the model depth, the
        bucket and the chunk (mamba2-780m at S=256 keeps 18 GB of layer
        residuals for 4 rows at chunk 1; ViT-S/16 fits at chunk 8), so the
        engine measures it: the compiler's refusal or its
        ``memory_analysis()`` against the device's ``bytes_limit``. Backends
        that report no limit (CPU) never remat. Returns the compiled program,
        the function it traced (the remat variant re-traces as remat for
        ``serve.warm_state``'s export) and whether it is the remat variant.
        ``sp``, the caller's compile span, is tagged when the compiler refuses."""

        def lower(f):
            with warnings.catch_warnings():
                # CPU cannot honor donation; the aliasing request is still
                # correct on every backend and must not spam serving logs
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers were not usable.*"
                )
                return (
                    jax.jit(f, donate_argnums=donate, **(jit_kw or {}))
                    .lower(*sds)
                    .compile()
                )

        limit = self._memory_limit()
        try:
            compiled = lower(fn)
            peak = _peak_bytes(compiled)
            if limit is None or peak is None or peak <= limit:
                return compiled, fn, False
        except Exception as e:  # noqa: BLE001 — only an out-of-memory refusal remats
            if limit is None or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            if sp is not None:
                sp.tag(refused=True)

        def remat_fn(*args):
            prev, self._remat = self._remat, True
            try:
                return fn(*args)
            finally:
                self._remat = prev

        return lower(remat_fn), remat_fn, True

    def _memory_limit(self) -> Optional[int]:
        """Bytes one executable may use on one device (``bytes_limit`` of
        ``memory_stats()``), or None where the backend reports none."""
        dev = (
            self.mesh.devices.flat[0] if self.mesh is not None else jax.devices()[0]
        )
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit")
        return int(limit) if limit else None

    def precompile_hop_zero_starts(self) -> int:
        """AOT-compile the start executables the δ-history now implies.

        History accumulates DURING a serving run, so the elevated starting
        rung ``_hop_zero_m`` would pick for a bucket may never have been
        compiled by that run (its own starts used the rung chosen when each
        batch arrived). ``save_warm_state`` calls this before serializing so
        a restored engine replays previously-seen buckets with zero compiles
        even where the restored history elevates the start. Shapes are free:
        the rung only changes program constants, so the elevated executable
        reuses the base start's recorded arg specs. Returns how many
        executables were added (not charged to serving stats — this is
        save-time work, not traffic)."""
        if not self.hop_zero:
            return 0
        n = 0
        for key in [k for k in self._cache if k[0] == "start"]:
            bucket, with_fx = key[1], key[-1]
            m0 = self._hop_zero_m(bucket)
            if m0 == key[4]:  # history picks this rung already
                continue
            info = self._export_info.get(key)
            if info is None or self._cache[key][1] is not None:
                continue  # sharded/unexportable — mesh engines recompile
            _, sds, donate = info
            new_key = (
                "start", bucket, self._spec.accum, self.schedule, m0,
                self.n_int, self._explainer_for_m(m0).adaptive_chunk,
                self.fused, self.use_kernels, self.attn, self._mesh_key,
                with_fx,
            )
            if new_key in self._cache:
                continue
            compiled, fn, _ = self._compile(self._start_fn_for(m0), sds, donate)
            self._cache[new_key] = (compiled, None)
            self._export_info[new_key] = (fn, sds, donate)
            n += 1
        return n

    # -- serving -----------------------------------------------------------

    def _prep(self, params, x, targets, lens, mask, idx, f_x=None) -> tuple:
        """A bucket's host arrays -> the attribution executable's arguments.

        Traced once per bucket shape into the bucket's prep program
        (``_bucket_inputs``), so the embedding, the ensemble draw and the mask
        draw run as one compiled call instead of one device op at a time.
        ``x`` is the (B, S) int token batch or the (B, S, *F) feature batch;
        ``idx`` (B,) uint32 holds each row's own request index (batch-pad rows
        repeat the last real one). What it returns is the branch the engine's
        method takes: ``(embeds, baseline, aux, mask)``, then ``f_x`` for a
        probe-reuse bucket, or the drawn masks ``z`` (and LIME's ``groups``)
        for a forward-only method."""
        spec = self._spec
        S = mask.shape[1]
        aux = {"target": targets, "pos": lens - 1}
        if jnp.issubdtype(x.dtype, jnp.integer):
            embeds = self.model.embed_inputs(params, {"tokens": x})
            # PAD-token embedding, not zeros: RMSNorm backbones are scale-
            # invariant through their first norm, so a ray through the origin
            # has (near-)zero gradient a.e. and completeness can never
            # converge.
            baseline = pad_embedding(
                params["embed"]["embedding"], embeds, pad_id=self.pad_id
            )
        else:
            # feature-space requests (ViT patches): the IG path interpolates
            # embedded features toward the embedded BLACK image (an affine
            # patch projection maps the paper's pixel-space straight line to
            # exactly this embedding-space line; the bias+posemb offset is
            # shared, so it is off-path-direction and the baseline gradient
            # is non-degenerate — unlike a zero embedding)
            embeds = self.model.embed_features(params, x)
            baseline = self.model.embed_features(params, jnp.zeros_like(x))
        # Each row's key is a pure function of ITS OWN (expanded) request
        # index, NOT a call counter and NOT the batch shape: replayed traffic
        # must draw the same ensemble and masks so its escalation path — and
        # therefore the set of hop shapes it touches — replays exactly (zero
        # recompiles), and a mesh-padded bucket (B rounded up to the dp
        # multiple, DESIGN.md §9) must draw the same per-row samples as the
        # single-device bucket (sharded parity).
        keys = jax.vmap(lambda i: perturb.request_key(self.sample_seed, S, i))(idx)
        if spec.expand is not None:
            # path-ensemble perturbation in embedding space: rows are already
            # replicated requests (see explain()), so each row draws its own
            # iid sample here — in the prep program, OUTSIDE the attribution
            # executables, which is what keeps ensemble methods on the shared
            # riemann executables
            e2, b2 = jax.vmap(
                lambda e, b, k: spec.expand(e[None], b[None], k, 1, self.sigma)
            )(embeds, baseline, keys)
            embeds, baseline = e2[:, 0], b2[:, 0]
        args = (embeds, baseline, aux, mask)
        if spec.forward_only:
            pm = perturb.draw_masks(spec.name, keys, S, self.n_masks)
            return args + ((pm.z,) if pm.groups is None else (pm.z, pm.groups))
        # probe-reuse bucket (docs/serving.md): the donated endpoint rides as
        # a trailing (B,) f32 argument. plan_buckets never mixes known-fx and
        # self-probing requests in one bucket, and explain() strips f_x for
        # ensemble methods before planning.
        return args if f_x is None else (*args, f_x)

    def _bucket_inputs(self, bb: BucketBatch) -> tuple:
        """The attribution executable's arguments for one bucket, built on
        the device by the bucket's prep program (``_prep``).

        One prep program per argument shape is compiled on first use and
        cached; its compile runs under the ``repro.engine.compile`` span and
        is counted in ``EngineStats.prep_compiles``/``prep_compile_s``, not
        in the executable cache's misses or the bucket rows. The returned
        arguments are dispatched, not waited for: the program's device time
        lands in the executable call's ``repro.engine.wait``. A forward-only
        bucket ignores a donated ``f_x``: callers strip it before planning,
        and the masks take its place in the argument tuple."""
        B, n = bb.bucket[0], len(bb.indices)
        idx = np.asarray([*bb.indices, *[bb.indices[-1]] * (B - n)], np.uint32)
        host = [
            bb.tokens if bb.features is None else bb.features,
            np.asarray(bb.targets, np.int32),
            np.asarray(bb.lens, np.int32),
            bb.mask,
            idx,
        ]
        if bb.f_x is not None and not self._spec.forward_only:
            host.append(np.asarray(bb.f_x, np.float32))
        key = ("prep", tuple((a.shape, a.dtype.str) for a in host))
        prep = self._prep_cache.get(key)
        if prep is None:
            self.stats.prep_compiles += 1
            sds = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (self.params, *host)
            )
            with span(self.stats, COMPILE, prep=True) as sp:
                prep = jax.jit(self._prep).lower(*sds).compile()
            self.stats.prep_compile_s += sp.seconds
            self._prep_cache[key] = prep
            self._prep_export_info[key] = (self._prep, sds, ())
        with span(self.stats, INPUTS, bucket=bb.bucket):
            return prep(self.params, *host)

    def _run_bucket(self, bb: BucketBatch) -> Any:
        args = self._bucket_inputs(bb)
        with_fx = bb.f_x is not None
        bs = self.stats.bucket(bb.bucket)
        ex = self._executable(
            self._key(bb.bucket, with_fx=with_fx), bs,
            self._attr_fn_at(self._cfg_for(bb.bucket)), args,
        )
        res = self._timed_call(bs, ex, args)
        bs.requests += len(bb.indices)
        return res

    # -- forward-only (perturbation) class ---------------------------------

    def _fwd_chunk(self) -> int:
        """Masks per scan step — the engine chunk when it divides P, else
        the whole mask batch (P is pow-2-sized by convention, so any pow-2
        chunk divides it)."""
        return self.chunk if self.chunk and self.n_masks % self.chunk == 0 else 0

    def _fwd_fn_at(self, cfg: HotpathConfig):
        """The compiled forward-evaluator unit: embeds + masks -> scores.

        Masks arrive as RUNTIME data drawn by the bucket's prep program
        (``_prep``, outside this executable, mirroring the path-ensemble
        contract), so one executable per (bucket, method, P) serves all
        replayed traffic. LIME's group map and ragged-group validity are
        pure in (bucket shape, mask) and recomputed inside the program —
        every argument stays batch-leading for the mesh sharding rule.
        ``use_kernels`` injects the Pallas WLS solve (``kernels/lstsq``)."""
        spec = self._spec
        chunk = self._fwd_chunk()
        solve = None
        if self.use_kernels:
            from repro.kernels.lstsq.ops import wls_solve

            solve = wls_solve
        if spec.accum == "lime":

            def fwd_lime(params, embeds, baseline, aux, mask, z, zg):
                G = zg.shape[-1]
                gids = perturb.lime_group_ids(embeds.shape[1], G)
                gvalid = perturb.group_real_mask(mask, gids, G)
                return perturb.attribute_from_masks(
                    self._f_for(cfg, params), embeds, baseline, aux,
                    perturb.PerturbMasks(z, zg, gids), method=spec, mask=mask,
                    group_valid=gvalid, chunk=chunk, solve_fn=solve,
                )

            return fwd_lime

        def fwd(params, embeds, baseline, aux, mask, z):
            return perturb.attribute_from_masks(
                self._f_for(cfg, params), embeds, baseline, aux,
                perturb.PerturbMasks(z), method=spec, mask=mask, chunk=chunk,
            )

        return fwd

    def _run_bucket_fwd(self, bb: BucketBatch) -> Any:
        """One forward-evaluator bucket call -> ``perturb.PerturbResult``
        (attributions are per POSITION (B, S), already exactly zero at
        pads). Its own executable key class: no schedule, no n_int — the
        mask budget P and the scan chunk are the program shape."""
        args = self._bucket_inputs(bb)
        bs = self.stats.bucket(bb.bucket)
        key = ("fwd", bb.bucket, self._spec.accum, self.n_masks,
               self._fwd_chunk(), self.use_kernels, self.attn, self._mesh_key)
        ex = self._executable(
            key, bs, self._fwd_fn_at(self._cfg_for(bb.bucket)), args
        )
        res = self._timed_call(bs, ex, args)
        bs.requests += len(bb.indices)
        return res

    def _replicated(self) -> Any:
        """Param shardings of a mesh executable: replicated on every device."""
        return jax.tree.map(lambda _: NamedSharding(self.mesh, P()), self.params)

    def _timed_call(self, bs: BucketStats, ex: tuple, args: tuple) -> Any:
        """Run one cached ``(compiled, shardings)`` entry; sharded inputs are
        placed onto the mesh first (host→device layout is part of the serving
        latency, so it stays inside the timed span). The params are placed
        once, replicated, on the first sharded call. The ``wait`` span inside
        the ``call`` span splits dispatch from the device's work."""
        compiled, shardings = ex
        with span(self.stats, CALL, bucket=args[0].shape[:2]) as sp:
            params = self.params
            if shardings is not None:
                args = jax.device_put(args, shardings)
                if self._mesh_params is None:
                    self._mesh_params = jax.device_put(self.params, self._replicated())
                params = self._mesh_params
            out = compiled(params, *args)
            with span(self.stats, WAIT):
                out = jax.block_until_ready(out)
        bs.total_s += sp.seconds
        bs.calls += 1
        return out

    def _run_bucket_adaptive(self, bb: BucketBatch) -> list[dict]:
        """δ-feedback serving for one bucket: rung 0, then escalate survivors.

        Returns one result dict per real request in ``bb.indices`` order.
        The ladder is an ``AdaptiveBucketRun`` driven to completion inline;
        the unified scheduler (``serve.scheduler``) drives the SAME object
        hop-by-hop instead, interleaving decode work between hops — both
        drivers hit identical executables and cache keys, so steady-state
        adaptive traffic never recompiles whichever path served it.
        """
        run = AdaptiveBucketRun(self, bb)
        run.start()
        while run.hop():
            pass
        return run.results()

    @staticmethod
    def _reduce_samples(group: list[dict]) -> dict:
        """Average one request's contiguous sample results (path-ensemble
        methods). δ is recomputed on the reduced quantities — the gap of the
        expectation, not the mean of per-sample gaps."""
        if len(group) == 1:
            return group[0]
        r = dict(group[0])
        mean = lambda k: np.mean([g[k] for g in group], axis=0)
        r["token_scores"] = mean("token_scores")
        if "raw_token_scores" in r:
            r["raw_token_scores"] = mean("raw_token_scores")
        r["f_x"] = float(mean("f_x"))
        r["f_baseline"] = float(mean("f_baseline"))
        r["delta"] = float(
            abs(float(np.sum(r["token_scores"])) - (r["f_x"] - r["f_baseline"]))
        )
        if "m_used" in r:  # adaptive: the request pays its worst sample
            r["m_used"] = max(g["m_used"] for g in group)
            r["hops"] = max(g["hops"] for g in group)
            r["threshold"] = float(mean("threshold"))
            r["converged"] = all(g["converged"] for g in group)
        return r

    def explain(
        self, requests: Sequence[ExplainRequest], *, return_raw: bool = False
    ) -> list[dict]:
        """Serve a heterogeneous batch; results align with ``requests``.

        With a ``result_cache``, each request's content key is consulted
        BEFORE ``plan_buckets``: hits replay the stored result dict
        bit-identically (a fresh copy — callers cannot corrupt the cache)
        and only misses are planned, bucketed, and computed. Degraded
        (fault-fallback) results are never cached. Everything below
        describes the compute path.
        """
        rc = self.result_cache
        if rc is None:
            return self._explain_uncached(requests, return_raw=return_raw)
        keys = [self.request_cache_key(r) for r in requests]
        results: list[Optional[dict]] = [rc.get(k) for k in keys]
        miss = [i for i, r in enumerate(results) if r is None]
        if miss:
            # always compute WITH raw rows so cached entries can serve both
            # return_raw variants; the caller-facing copy is trimmed below
            fresh = self._explain_uncached(
                [requests[i] for i in miss], return_raw=True
            )
            for i, r in zip(miss, fresh):
                if not r.get("degraded"):
                    rc.put(keys[i], r)
                results[i] = r
        self._sync_result_stats()
        if not return_raw:
            for r in results:
                r.pop("raw_token_scores", None)
        return results

    def _explain_uncached(
        self, requests: Sequence[ExplainRequest], *, return_raw: bool = False
    ) -> list[dict]:
        """The compute path (``explain`` without the result cache).

        Each result dict: token_scores (S_req,), delta, f_x, f_baseline,
        bucket (B, S); with ``return_raw`` also raw_token_scores (S_bucket,)
        — the untrimmed row, exactly zero at padded positions. In adaptive
        mode every dict additionally reports ``m_used`` (the rung the request
        exited at), ``hops``, ``threshold`` (tol·|f_x − f_baseline|) and
        ``converged``.

        Path-ensemble methods (noise_tunnel / expected_grad): each request is
        replicated ``n_samples``× at plan time, rows are perturbed in
        embedding space at batch construction, and each request's sample
        results are averaged back into one dict — so the per-request
        contract above is method-independent.
        """
        n = self.n_samples
        if n == 1:
            expanded = list(requests)
        else:
            # ensemble rows perturb the input in embedding space, so a
            # decode-donated endpoint value is for the WRONG point — strip it
            # before planning (requests fall back to self-probing buckets)
            expanded = [
                replace(r, f_x=None) if r.f_x is not None else r
                for r in requests
                for _ in range(n)
            ]
        if self._spec.forward_only:
            # forward-only buckets always compute both endpoints inside the
            # program (a donated f_x would fork the executable key class for
            # no gradient saved — there are no gradients), so strip it and
            # keep ONE compiled program per (bucket, method, P)
            expanded = [
                replace(r, f_x=None) if r.f_x is not None else r
                for r in expanded
            ]
        plan = plan_buckets(
            expanded,
            seq_buckets=self.seq_buckets,
            batch_buckets=self.batch_buckets,
            max_batch=self.max_batch,
            pad_id=self.pad_id,
            batch_multiple=self.dp,
        )
        out: list[Optional[dict]] = [None] * len(expanded)
        for bb in plan:
            if self.adaptive:
                for r in self._run_bucket_adaptive(bb):
                    ri = r.pop("request")
                    if not return_raw:
                        r.pop("raw_token_scores")
                    out[ri] = r
                continue
            if self._spec.forward_only:
                res = self._run_bucket_fwd(bb)
                # perturbation scores are already per POSITION (B, S) —
                # there is no feature axis to reduce
                per_token = np.asarray(res.attributions)
            else:
                res = self._run_bucket(bb)
                per_token = np.asarray(res.attributions.sum(-1))  # (B, S)
            for row, ri in enumerate(bb.indices):
                r = {
                    "token_scores": per_token[row, : bb.lens[row]],
                    "delta": float(res.delta[row]),
                    "f_x": float(res.f_x[row]),
                    "f_baseline": float(res.f_baseline[row]),
                    "bucket": bb.bucket,
                }
                if return_raw:
                    r["raw_token_scores"] = per_token[row]
                out[ri] = r
        if n == 1:
            return out
        return [
            self._reduce_samples(out[i * n : (i + 1) * n])
            for i in range(len(requests))
        ]


class AdaptiveBucketRun:
    """One bucket's δ-adaptive ladder as explicit, preemptible work items.

    The classic engine path (``ExplainEngine._run_bucket_adaptive``) drives
    this to completion inline; the unified scheduler (``serve.scheduler``)
    interleaves ``hop()`` calls with decode work instead — each hop is one
    compiled executable call over the still-unconverged survivors, so decode
    traffic preempts BETWEEN hops, never inside a compiled program. Hop
    executables and their cache keys are byte-identical on both drivers, so
    mixed and standalone traffic warm ONE shared executable set (the
    zero-steady-state-recompile invariant extends across the scheduler).

    Protocol:
      * ``start()`` — rung 0: probe + base schedule + resumable stage 2
        (honors a donated ``bb.f_x`` endpoint, see docs/serving.md);
      * while ``active``: ``hop()`` escalates the survivors one rung and
        returns whether work remains;
      * ``degrade()`` — abandon the remaining ladder: the current rung's
        results stand as the fallback (they are complete attributions, just
        less converged than tol demands); affected rows are marked
        ``degraded`` and counted on ``EngineStats.degraded``;
      * ``results()`` — finalize the adaptive stats (once) and return one
        dict per real request in ``bb.indices`` order.
    """

    def __init__(self, engine: ExplainEngine, bb: BucketBatch):
        self.eng = engine
        self.bb = bb
        self._started = False
        self._results: Optional[list[dict]] = None
        self._degraded: set[int] = set()
        self._rung_i = 1  # next ladder index to run (0 is start())
        self.act: list[int] = []

    @property
    def active(self) -> bool:
        """More ladder hops pending (unconverged survivors + rungs left)."""
        return bool(self.act) and self._rung_i < len(self.eng.m_ladder)

    def start(self) -> None:
        eng, bb = self.eng, self.bb
        assert not self._started
        self._started = True
        # hop-zero (engine._hop_zero_m): with enough per-(S, method) history
        # the ladder starts at the historical-quantile rung m0 >= m; cold
        # buckets keep the base rung, so their traces are unchanged. The
        # start key carries m0 and the rung's chunk — the m0 set is the
        # ladder, so the executable set stays closed.
        self.m0 = eng._hop_zero_m(bb.bucket)
        self._rung_i = eng.m_ladder.index(self.m0) + 1
        self.chunk = eng._explainer_for_m(self.m0).adaptive_chunk
        with_fx = bb.f_x is not None
        args = eng._bucket_inputs(bb)
        key = ("start", bb.bucket, eng._spec.accum, eng.schedule, self.m0,
               eng.n_int, self.chunk, eng.fused, eng.use_kernels, eng.attn,
               eng._mesh_key, with_fx)
        bs = eng.stats.bucket(bb.bucket)
        ex = eng._executable(key, bs, eng._start_fn_for(self.m0), args)
        res, state, sched = eng._timed_call(bs, ex, args)
        bs.requests += len(bb.indices)

        n_real = len(bb.indices)
        ast = eng.stats.adaptive
        ast.requests += n_real
        ast.total_steps += n_real * self.m0
        ast.launched_steps += bb.bucket[0] * self.m0
        # per-real-request like total_steps (pad-row forwards are launch
        # overhead, visible via launched_steps' bucket padding instead); a
        # donated endpoint saves the α=1 probe forward per row
        ast.probe_forwards += n_real * probe_cost(
            family(eng.schedule).probe,
            n_int=eng.n_int,
            rounds=eng._explainer.refine_rounds,
            known_fx=with_fx,
        )

        with span(eng.stats, READBACK):
            embeds, baseline, aux, mask = args[:4]
            self.embeds = np.asarray(embeds)
            self.baseline = np.asarray(baseline)
            self.aux = {k: np.asarray(v) for k, v in aux.items()}
            self.mask = np.asarray(mask)
            self.delta = np.asarray(res.delta).copy()
            self.f_x = np.asarray(res.f_x)
            self.f_b = np.asarray(res.f_baseline)
            self.threshold = eng.tol * np.abs(self.f_x - self.f_b)
            self.per_token = np.asarray(res.attributions.sum(-1)).copy()  # (B, S)
            self.m_used = np.full((bb.bucket[0],), self.m0, np.int64)
            self.hops = np.zeros((bb.bucket[0],), np.int64)

            # survivors: real rows whose δ still exceeds tol·|f_x − f_b|
            self.act = [r for r in range(n_real) if self.delta[r] > self.threshold[r]]
            self.a_act = np.asarray(sched.alphas)[self.act]
            self.w_act = np.asarray(sched.weights)[self.act]
            self.acc_act = np.asarray(state.acc)[self.act]

    def hop(self) -> bool:
        """Run ONE escalation rung over the survivors; returns ``active``.

        Escalation re-batches still-unconverged rows together (batch axis
        padded up the batch ladder by duplicating a survivor, as at plan
        time) and runs ONLY the refined schedule's new nodes through hop
        executables keyed ``("hop", (B', S), n_new, chunk)`` — a closed shape
        set, so steady-state adaptive traffic never recompiles.
        """
        if not self.active:
            return False
        eng, act = self.eng, self.act
        S = self.bb.bucket[1]
        rung = eng.m_ladder[self._rung_i]
        self._rung_i += 1
        n_new = rung // 2
        with span(eng.stats, REFINE, rung=rung):
            refined = family(eng.schedule).refine(
                Schedule(jnp.asarray(self.a_act), jnp.asarray(self.w_act))
            )
            ra, rw = np.asarray(refined.alphas), np.asarray(refined.weights)
        with span(eng.stats, GATHER, rows=len(act)) as sp:
            rows, B2 = pad_rows(act, eng.batch_buckets, multiple=eng.dp)
            sp.tag(B=B2)
            # schedule/state slot per padded row: pad_rows keeps act as a
            # prefix and repeats the last real row into the pad slots
            pad_sel = list(range(len(act))) + [len(act) - 1] * (B2 - len(act))
            hop_bucket = (B2, S)
            hop_args = (
                self.embeds[rows],
                self.baseline[rows],
                {k: v[rows] for k, v in self.aux.items()},
                self.mask[rows],
                Schedule(ra[pad_sel, n_new:], rw[pad_sel, n_new:]),
                ig.IGState(self.acc_act[pad_sel], self.f_x[rows], self.f_b[rows]),
            )
        hop_key = ("hop", hop_bucket, eng._spec.accum, n_new, self.chunk,
                   eng.fused, eng.use_kernels, eng.attn, eng._mesh_key)
        hbs = eng.stats.hop_bucket(hop_bucket)
        # the IGState (arg 5) is donated: escalation reuses the (B, *F)
        # f32 accumulator buffer in place instead of copying each rung
        # (DESIGN.md §10); it is rebuilt fresh per hop and never read
        # back after the call, so donation is always safe here
        hop = eng._executable(
            hop_key, hbs, eng._hop_fn_for(self.m0), hop_args, donate=(5,)
        )
        res2, st2 = eng._timed_call(hbs, hop, hop_args)
        ast = eng.stats.adaptive
        ast.hop_calls += 1
        ast.launched_steps += B2 * n_new
        ast.total_steps += len(act) * n_new

        with span(eng.stats, READBACK):
            d2 = np.asarray(res2.delta)
            pt2 = np.asarray(res2.attributions.sum(-1))
            acc2 = np.asarray(st2.acc)
            keep = []
            for slot, r in enumerate(act):  # real survivors occupy slots [0, len(act))
                self.delta[r] = d2[slot]
                self.per_token[r] = pt2[slot]
                self.m_used[r] = rung
                self.hops[r] += 1
                if d2[slot] > self.threshold[r]:
                    keep.append(slot)
            self.act = [act[s] for s in keep]
            self.a_act, self.w_act = ra[keep], rw[keep]
            self.acc_act = acc2[keep]
        return self.active

    def degrade(self) -> int:
        """Abandon the remaining ladder; current-rung results become the
        fallback. Returns how many real rows were degraded (each counted on
        ``EngineStats.degraded``). Idempotent once drained."""
        n = len(self.act)
        if n:
            self._degraded.update(self.act)
            self.eng.stats.degraded += n
            self.act = []
        return n

    def results(self) -> list[dict]:
        """One result dict per real request (``bb.indices`` order); finalizes
        the aggregate adaptive counters exactly once."""
        if self._results is not None:
            return self._results
        eng, bb = self.eng, self.bb
        ast = eng.stats.adaptive
        out = []
        for row, ri in enumerate(bb.indices):
            converged = bool(self.delta[row] <= self.threshold[row])
            ast.converged += converged
            ast.early_exits += converged and int(self.m_used[row]) < eng.m_ladder[-1]
            mu = int(self.m_used[row])
            ast.m_used[mu] = ast.m_used.get(mu, 0) + 1
            out.append(
                {
                    "request": ri,
                    "token_scores": self.per_token[row, : bb.lens[row]],
                    "raw_token_scores": self.per_token[row],
                    "delta": float(self.delta[row]),
                    "threshold": float(self.threshold[row]),
                    "f_x": float(self.f_x[row]),
                    "f_baseline": float(self.f_b[row]),
                    "bucket": bb.bucket,
                    "m_used": mu,
                    "hops": int(self.hops[row]),
                    "converged": converged,
                    "degraded": row in self._degraded,
                }
            )
        # hop-zero evidence: ONLY base-rung starts contribute (an elevated
        # start's m_used is floored at m0 — feeding it back would ratchet
        # the quantile upward forever); degraded rows never converged by
        # fiat, not by δ, so they are no evidence either
        if self.m0 == eng.m:
            eng._record_m_used(
                bb.bucket[1],
                [r["m_used"] for r in out if not r["degraded"]],
            )
        self._results = out
        return out
