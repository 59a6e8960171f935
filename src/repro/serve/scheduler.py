"""MixedScheduler — one admission-controlled queue for generate AND explain.

The repo's two serving halves historically ran split-brain: ``ServeEngine``
decoded with a donated-cache ``lax.scan`` while ``ExplainEngine`` re-ran the
same forwards from scratch in a separate process. This module fuses them
behind one bounded request queue, so a real mixed workload pays the model
once and gets admission control:

  * **Bounded queue, backpressure, per-tenant rate/priority classes** —
    ``submit()`` rejects (never blocks, never drops silently) when the queue
    is full (``rejected_backpressure``) or the tenant's token bucket is dry
    (``rejected_rate``); every request carries an ``SLOClass`` whose priority
    orders the dispatch heap.
  * **KV/logit probe reuse** — a generate request with ``explain=True``
    attributes its prompt toward the first emitted token by DONATING the
    decode prefill's chosen-token log-prob as the explain stage-1 endpoint
    ``f(x)`` (``ExplainRequest.f_x``): the α=1 probe forward and the
    completeness endpoint forward are never re-run. At float32 compute the
    donated value is bit-identical to the forward the standalone engine
    would have run (benchmarks/mixed_serving.py gates this); later streamed
    positions (``explain_stream=True``) ride the same executables without a
    donated endpoint, because incremental decode-step logits are NOT bitwise
    equal to a fresh forward (softmax over the padded KV buffer
    reassociates) and the reuse contract refuses to donate approximations.
  * **δ-aware preemption** — adaptive escalation hops
    (``explain_engine.AdaptiveBucketRun``) are the scheduler's lowest
    -priority work items: decode chunks always dispatch ahead of pending
    hops (each deferral counted on ``EngineStats.preempted``), so explain
    traffic can never starve decode; conversely every hop that does run uses
    exactly the executables standalone serving warmed (shared cache keys —
    the zero-steady-state-recompile invariant spans both traffic kinds).
  * **Fault degradation, not death** — every model-executing item runs under
    ``runtime.fault.RetryPolicy``; on exhaustion the AFFECTED requests
    degrade to a fallback result (decode keeps the tokens emitted so far,
    explain falls back to the last completed rung or zero scores) and the
    engine keeps serving. A ``StragglerMonitor`` observes per-item wall
    times. ``EngineStats`` carries the ``degraded``/``preempted``/
    ``queue_depth`` counters.

The dispatch loop is synchronous and cooperative (``step()`` runs exactly
one work item): preemption happens BETWEEN compiled-program calls, which is
the only place it can happen on an accelerator anyway, and the loop is
driven either inline (``run_until_idle``) or from a host event loop.
"""
from __future__ import annotations

import heapq
import time
import warnings
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.fault import FaultConfig, RetryPolicy, StragglerMonitor
from repro.serve.batching import bucket_for, pad_rows, plan_buckets
from repro.serve.engine import make_decode_chunk, make_prefill_step, sample_token
from repro.serve.explain_engine import (
    AdaptiveBucketRun,
    BucketStats,
    ExplainEngine,
    ExplainRequest,
)

# -- request classes ---------------------------------------------------------


@dataclass(frozen=True)
class SLOClass:
    """A latency class: ``priority`` orders the dispatch heap (lower = more
    urgent); ``target_p99_ms`` is the class's reported SLO target (0 = none)."""

    name: str
    priority: int
    target_p99_ms: float = 0.0


INTERACTIVE = SLOClass("interactive", 0, 150.0)
BATCH = SLOClass("batch", 1, 1500.0)
EXPLAIN = SLOClass("explain", 2, 0.0)

# hop items sit BELOW every request class: δ-escalation is strictly
# best-effort work and must never starve decode (ISSUE 8 / ROADMAP)
_PRIO_EXPLAIN_WORK = 10
_PRIO_HOP = 20


@dataclass(frozen=True)
class TenantPolicy:
    """Token-bucket admission: ``rate`` requests/s refill, ``burst`` capacity."""

    rate: float = float("inf")
    burst: int = 8


@dataclass(frozen=True)
class GenerateRequest:
    """A decode request, optionally with attribution riding along.

    ``explain=True`` attributes the prompt toward the FIRST emitted token
    with the donated-endpoint contract (bit-exact at f32 compute);
    ``explain_stream=True`` additionally attributes every later emitted
    token (prompt+prefix → token) as tokens stream out — those ride the same
    warmed explain executables but self-probe (no donated endpoint; see the
    module docstring for why). ``seed=None`` decodes greedily; a seed
    samples at ``temperature``.
    """

    tokens: np.ndarray  # (S,) int32 prompt
    num_tokens: int
    tenant: str = "default"
    slo: SLOClass = INTERACTIVE
    explain: bool = False
    explain_stream: bool = False
    temperature: float = 0.0
    seed: Optional[int] = None


@dataclass
class Ticket:
    """The caller's handle: filled in as the scheduler makes progress.

    ``status`` ∈ queued | running | done | degraded | rejected_backpressure |
    rejected_rate. ``tokens`` accumulates emitted ids; ``attributions``
    accumulates per-position explain result dicts (each tagged ``pos`` /
    ``token``) in emission order; explain-only tickets get ``result``.
    """

    id: int
    kind: str  # "generate" | "explain"
    status: str = "queued"
    tenant: str = "default"
    slo: SLOClass = EXPLAIN
    tokens: Optional[np.ndarray] = None
    attributions: list = field(default_factory=list)
    result: Optional[dict] = None
    degraded: bool = False
    submitted_s: float = 0.0
    finished_s: float = 0.0
    # internal completion tracking
    _decode_done: bool = False
    _pending_explains: int = 0

    @property
    def latency_s(self) -> float:
        return self.finished_s - self.submitted_s


class _TokenBucket:
    def __init__(self, policy: TenantPolicy, time_fn: Callable[[], float]):
        self.policy = policy
        self.tokens = float(policy.burst)
        self.time_fn = time_fn
        self._t = time_fn()

    def try_take(self) -> bool:
        now = self.time_fn()
        if self.policy.rate != float("inf"):
            self.tokens = min(
                float(self.policy.burst),
                self.tokens + (now - self._t) * self.policy.rate,
            )
        self._t = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


# -- internal work-item payloads --------------------------------------------


@dataclass
class _GenGroup:
    """Same-shape generate requests batched for one prefill + decode stream.

    Grouping key: exact prompt length (prefill logits of a padded prompt
    would attend over pad tokens — NOT the same forward, so no padding in S),
    num_tokens, and the sampling config. The batch axis pads up the batch
    ladder by repeating the last row; pad-row outputs are dropped.
    """

    tickets: list  # real tickets, row-aligned with prompts
    requests: list  # the GenerateRequests, row-aligned with tickets
    prompts: np.ndarray  # (B_pad, S) int32
    n_real: int
    num_tokens: int
    temperature: float
    seed: Optional[int]
    priority: int


@dataclass
class _DecodeStream:
    group: _GenGroup
    cache: Any  # device KV cache, carried chunk to chunk
    last_tok: Any  # (B, 1) device
    remaining: int
    emitted: int  # tokens emitted per row so far (incl. the prefill token)


class MixedScheduler:
    """The unified serving path over one ``ExplainEngine``'s model+params.

    Decode executables (prefill per exact (B, S), decode chunks) are
    AOT-compiled into the scheduler's own cache but counted on the ENGINE's
    hit/miss stats — the "combined executable set" the zero-recompile gate
    watches is one set. Explain work goes through the engine's own buckets,
    start/hop executables and stats, so mixed and standalone traffic are
    indistinguishable to the compile cache.

    Args:
        engine: the ``ExplainEngine`` (its cfg/params also serve decode).
        max_len: static KV-cache length (prompt+generation must fit).
        max_queue: bounded-queue capacity (backpressure above it).
        decode_chunk: tokens per preemptible decode work item.
        tenants: name → ``TenantPolicy`` (absent tenants are unlimited).
        fault_cfg / time_fn: fault policy knobs and the clock (injectable
            for tests).
    """

    def __init__(
        self,
        engine: ExplainEngine,
        *,
        max_len: int = 128,
        max_queue: int = 64,
        decode_chunk: int = 8,
        tenants: Optional[dict] = None,
        fault_cfg: FaultConfig = FaultConfig(backoff_base_s=0.0),
        time_fn: Callable[[], float] = time.monotonic,
    ):
        assert engine.n_samples == 1, (
            "MixedScheduler serves per-row methods; path-ensemble methods "
            "(n_samples > 1) go through ExplainEngine.explain directly"
        )
        self.engine = engine
        self.max_len = max_len
        self.max_queue = max_queue
        self.decode_chunk = decode_chunk
        self.tenants = tenants or {}
        self.time_fn = time_fn
        self._buckets = {
            name: _TokenBucket(pol, time_fn) for name, pol in self.tenants.items()
        }
        self.retry = RetryPolicy(fault_cfg)
        self.monitor = StragglerMonitor(fault_cfg)
        # test/benchmark fault injection: called as fault_hook(kind, payload)
        # at the top of every (retried) work-item attempt; raise to inject a
        # failure, sleep to inject a straggler
        self.fault_hook: Optional[Callable[[str, Any], None]] = None

        self._prefill_fn = make_prefill_step(engine.cfg, max_len)
        self._chunk_fn = make_decode_chunk(engine.cfg)
        self._exec_cache: dict[tuple, Any] = {}
        self.decode_stats: dict[tuple, BucketStats] = {}

        self._heap: list = []  # (priority, seq, kind, payload)
        self._seq = 0
        self._next_id = 0
        self.tickets: list[Ticket] = []
        self._pending_gen: list[tuple[Ticket, GenerateRequest]] = []
        self._pending_exp: list[tuple[Ticket, int, Optional[int], ExplainRequest]] = []
        self._gen_flush_queued = False
        self._exp_flush_queued = False
        self.latencies: dict[str, list[float]] = {}
        self.rejected_backpressure = 0
        self.rejected_rate = 0

    # -- admission -----------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self._heap) + len(self._pending_gen) + len(self._pending_exp)

    def submit(
        self,
        req: Union[GenerateRequest, ExplainRequest],
        *,
        tenant: str = "default",
        slo: Optional[SLOClass] = None,
    ) -> Ticket:
        """Admit one request; returns its ``Ticket`` immediately.

        Rejection (full queue / dry tenant bucket) and admission-time
        degradation (a prompt no ladder rung or the KV cache can hold — a
        poisoned request must not reach, and kill, the dispatch loop) are
        reported on the ticket, never raised.
        """
        is_gen = isinstance(req, GenerateRequest)
        t = Ticket(
            id=self._next_id,
            kind="generate" if is_gen else "explain",
            tenant=req.tenant if is_gen else tenant,
            slo=(slo or req.slo) if is_gen else (
                slo or (BATCH if self.engine._spec.forward_only else EXPLAIN)
            ),
            submitted_s=self.time_fn(),
        )
        self._next_id += 1
        self.tickets.append(t)
        if not is_gen:
            hit = self._cached_result(req)
            if hit is not None:
                # content-addressed replay (serve.result_cache): a hit is
                # admitted BEFORE backpressure and rate checks — it costs no
                # queue slot, no tenant budget, and never preempts decode,
                # so cached traffic cannot push fresh traffic into rejection
                t.result = hit
                t._decode_done = True
                t._pending_explains = 0
                self._finish(t)
                return t
        if self.queue_depth >= self.max_queue:
            t.status = "rejected_backpressure"
            self.rejected_backpressure += 1
            return t
        bucket = self._buckets.get(t.tenant)
        if bucket is not None and not bucket.try_take():
            t.status = "rejected_rate"
            self.rejected_rate += 1
            return t
        try:  # poisoned-size admission check: degrade, don't explode later
            bucket_for(len(req.tokens), self.engine.seq_buckets)
            if is_gen and len(req.tokens) + req.num_tokens > self.max_len:
                raise ValueError("prompt + generation exceeds KV capacity")
        except ValueError:
            self._degrade_ticket(t, reason="admission")
            return t
        if is_gen:
            if req.num_tokens <= 0:
                t.tokens = np.zeros((0,), np.int32)
                self._finish(t)
                return t
            t.tokens = np.zeros((0,), np.int32)
            self._pending_gen.append((t, req))
            if not self._gen_flush_queued:
                self._gen_flush_queued = True
                self._push(t.slo.priority, "gen_flush", None)
        else:
            t._pending_explains = 1
            t._decode_done = True
            self._pending_exp.append((t, -1, None, req))
            if not self._exp_flush_queued:
                self._exp_flush_queued = True
                self._push(_PRIO_EXPLAIN_WORK, "exp_flush", None)
        return t

    # -- dispatch loop -------------------------------------------------------

    def _push(self, priority: int, kind: str, payload: Any) -> None:
        heapq.heappush(self._heap, (priority, self._seq, kind, payload))
        self._seq += 1

    def step(self) -> bool:
        """Dispatch exactly one work item; False when idle."""
        if not self._heap:
            return False
        self.engine.stats.queue_depth = self.queue_depth
        prio, _, kind, payload = heapq.heappop(self._heap)
        if kind in ("prefill", "decode") and any(
            k in ("hop", "exp_fwd") for _, _, k, _ in self._heap
        ):
            # δ-aware preemption: this decode work runs AHEAD of queued
            # escalation hops — count the deferral. Forward-only mask
            # batches (``exp_fwd``) sit at the same rung: they are BATCH
            # -class throughput work that always yields to latency traffic
            self.engine.stats.preempted += 1
        handler = {
            "gen_flush": self._do_gen_flush,
            "exp_flush": self._do_exp_flush,
            "prefill": self._do_prefill,
            "decode": self._do_decode,
            "exp_fixed": self._do_exp_fixed,
            "exp_fwd": self._do_exp_fwd,
            "exp_start": self._do_exp_start,
            "hop": self._do_hop,
        }[kind]
        handler(payload)
        self.engine.stats.queue_depth = self.queue_depth
        return True

    def run_until_idle(self) -> None:
        while self.step():
            pass

    # -- flush markers: coalesce pending requests into batched items ---------

    def _do_gen_flush(self, _payload) -> None:
        self._gen_flush_queued = False
        pending, self._pending_gen = self._pending_gen, []
        groups: dict[tuple, list[tuple[Ticket, GenerateRequest]]] = {}
        for t, r in pending:
            key = (len(r.tokens), r.num_tokens, r.temperature, r.seed)
            groups.setdefault(key, []).append((t, r))
        for (S, num_tokens, temp, seed), members in groups.items():
            rows, B = pad_rows(
                list(range(len(members))), self.engine.batch_buckets
            )
            prompts = np.stack(
                [np.asarray(members[i][1].tokens, np.int32) for i in rows]
            )
            grp = _GenGroup(
                tickets=[m[0] for m in members],
                requests=[m[1] for m in members],
                prompts=prompts,
                n_real=len(members),
                num_tokens=num_tokens,
                temperature=temp,
                seed=seed,
                priority=min(m[0].slo.priority for m in members),
            )
            self._push(grp.priority, "prefill", grp)

    def _do_exp_flush(self, _payload) -> None:
        self._exp_flush_queued = False
        pending, self._pending_exp = self._pending_exp, []
        forward_only = self.engine._spec.forward_only
        if forward_only:
            # forward-only buckets self-probe both endpoints inside ONE
            # executable class — a decode-donated f_x would fork the compile
            # key for nothing (there is no gradient pass to save)
            pending = [
                (t, pos, tok, replace(r, f_x=None) if r.f_x is not None else r)
                for (t, pos, tok, r) in pending
            ]
        reqs = [p[3] for p in pending]
        plan = plan_buckets(
            reqs,
            seq_buckets=self.engine.seq_buckets,
            batch_buckets=self.engine.batch_buckets,
            max_batch=self.engine.max_batch,
            pad_id=self.engine.pad_id,
            batch_multiple=self.engine.dp,
        )
        for bb in plan:
            reqmap = [pending[i] for i in bb.indices]
            if forward_only:
                # perturbation mask batches are preemptible BATCH-class
                # work: queued at the hop rung so interactive decode always
                # dispatches first (and counts the deferral, step())
                self._push(_PRIO_HOP, "exp_fwd", (bb, reqmap))
            elif self.engine.adaptive:
                run = AdaptiveBucketRun(self.engine, bb)
                self._push(_PRIO_EXPLAIN_WORK, "exp_start", (run, reqmap))
            else:
                self._push(_PRIO_EXPLAIN_WORK, "exp_fixed", (bb, reqmap))

    # -- decode items --------------------------------------------------------

    def _aot(self, key: tuple, fn, args: tuple, *, static=(), donate=()):
        """AOT-compile one decode executable; counted on the ENGINE's
        hit/miss stats so the mixed path's compile set is one set."""
        ent = self._exec_cache.get(key)
        if ent is not None:
            self.engine.stats.hits += 1
            return ent
        self.engine.stats.misses += 1
        bs = self.decode_stats.setdefault(key, BucketStats())
        bs.compiles += 1
        t0 = time.perf_counter()
        sds = [
            a if i in static
            else jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)
            for i, a in enumerate(args)
        ]
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            ent = (
                jax.jit(fn, static_argnums=static, donate_argnums=donate)
                .lower(*sds)
                .compile()
            )
        bs.compile_s += time.perf_counter() - t0
        self._exec_cache[key] = ent
        return ent

    def _do_prefill(self, grp: _GenGroup) -> None:
        B, S = grp.prompts.shape
        batch = {"tokens": jnp.asarray(grp.prompts)}
        ex = self._aot(
            ("dprefill", B, S), self._prefill_fn, (self.engine.params, batch)
        )
        ok, out = self._run_item("prefill", grp, lambda: ex(self.engine.params, batch))
        if not ok:
            for t in grp.tickets:
                self._degrade_ticket(t, reason="prefill")
            return
        logits, cache = out
        lg = logits[:, -1].astype(jnp.float32)
        if grp.seed is None:
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            tok = sample_token(
                lg,
                jax.random.fold_in(jax.random.PRNGKey(grp.seed), 2**32 - 1),
                jnp.asarray(grp.temperature, jnp.float32),
            )
        # the chosen token's log-prob IS the explain endpoint f(x) — the
        # donated-probe contract (module docstring; bit-exact at f32)
        lp = jax.nn.log_softmax(lg, axis=-1)[jnp.arange(lg.shape[0]), tok]
        tok_np, lp_np = np.asarray(tok), np.asarray(lp)
        for row in range(grp.n_real):
            t, req = grp.tickets[row], grp.requests[row]
            t.status = "running"
            t.tokens = np.append(t.tokens, tok_np[row]).astype(np.int32)
            if req.explain:
                self._enqueue_explain(
                    t,
                    pos=0,
                    token=int(tok_np[row]),
                    prompt=np.asarray(req.tokens, np.int32),
                    f_x=float(lp_np[row]),
                )
        if grp.num_tokens > 1:
            stream = _DecodeStream(
                group=grp,
                cache=cache,
                last_tok=tok[:, None],
                remaining=grp.num_tokens - 1,
                emitted=1,
            )
            self._push(grp.priority, "decode", stream)
        else:
            for t in grp.tickets:
                t._decode_done = True
                self._maybe_finish(t)

    def _do_decode(self, st: _DecodeStream) -> None:
        grp = st.group
        n = min(self.decode_chunk, st.remaining)
        B = grp.prompts.shape[0]
        seed = grp.seed if grp.seed is not None else 0
        key = jax.random.fold_in(jax.random.PRNGKey(seed), st.emitted)
        temp = jnp.asarray(
            grp.temperature if grp.seed is not None else 0.0, jnp.float32
        )
        ex = self._aot(
            ("dchunk", B, n),
            self._chunk_fn,
            (self.engine.params, st.cache, st.last_tok, key, temp, n),
            static=(5,),
            donate=(1,),
        )
        ok, out = self._run_item(
            "decode",
            st,
            lambda: ex(self.engine.params, st.cache, st.last_tok, key, temp),
        )
        if not ok:
            # the cache may have been donated into the failed call: the
            # emitted-so-far prefix is the fallback result
            for t in grp.tickets:
                self._degrade_ticket(t, reason="decode", keep_tokens=True)
            return
        toks, lps, st.cache = out
        toks_np = np.asarray(toks)
        for row in range(grp.n_real):
            t, req = grp.tickets[row], grp.requests[row]
            if t.degraded:
                continue
            for k in range(n):
                pos = st.emitted + k
                tok_id = int(toks_np[row, k])
                t.tokens = np.append(t.tokens, tok_id).astype(np.int32)
                if req.explain_stream:
                    # streamed positions self-probe: incremental decode-step
                    # logits are not bitwise a fresh forward, so no donation
                    prefix = np.concatenate(
                        [np.asarray(req.tokens, np.int32), t.tokens[:pos]]
                    )
                    self._enqueue_explain(
                        t, pos=pos, token=tok_id, prompt=prefix, f_x=None
                    )
        st.last_tok = toks[:, -1:]
        st.remaining -= n
        st.emitted += n
        if st.remaining > 0:
            self._push(grp.priority, "decode", st)
        else:
            for t in grp.tickets:
                t._decode_done = True
                self._maybe_finish(t)

    # -- explain items -------------------------------------------------------

    def _cached_result(self, req: ExplainRequest) -> Optional[dict]:
        """Consult the engine's content-addressed result cache (a fresh copy
        on hit, raw row trimmed — tickets carry caller-facing dicts)."""
        rc = self.engine.result_cache
        if rc is None:
            return None
        hit = rc.get(self.engine.request_cache_key(req))
        self.engine._sync_result_stats()
        if hit is not None:
            hit.pop("raw_token_scores", None)
        return hit

    def _cache_result(self, req: ExplainRequest, r: dict) -> None:
        """Insert one finished result (degraded fallbacks are never cached —
        replaying a fault-path zero vector forever would be wrong)."""
        rc = self.engine.result_cache
        if rc is not None and not r.get("degraded"):
            rc.put(self.engine.request_cache_key(req), r)
            self.engine._sync_result_stats()

    def _enqueue_explain(
        self,
        t: Ticket,
        *,
        pos: int,
        token: int,
        prompt: np.ndarray,
        f_x: Optional[float],
    ) -> None:
        if len(prompt) > max(self.engine.seq_buckets):
            self._deliver_degraded(t, pos, token, n_tokens=len(prompt))
            return
        req = ExplainRequest(tokens=prompt, target=token, f_x=f_x)
        hit = self._cached_result(req)
        if hit is not None:
            # per-token replay for generate+explain tickets: this position's
            # attribution never reaches the explain queue
            t._pending_explains += 1
            self._deliver(t, pos, token, hit)
            return
        t._pending_explains += 1
        self._pending_exp.append((t, pos, token, req))
        if not self._exp_flush_queued:
            self._exp_flush_queued = True
            self._push(_PRIO_EXPLAIN_WORK, "exp_flush", None)

    def _do_exp_fixed(self, payload) -> None:
        bb, reqmap = payload
        ok, res = self._run_item(
            "exp_fixed", bb, lambda: self.engine._run_bucket(bb)
        )
        if not ok:
            self.engine.stats.degraded += len(reqmap)
            for (t, pos, token, req) in reqmap:
                self._deliver_degraded(t, pos, token, n_tokens=len(req.tokens))
            return
        per_token = np.asarray(res.attributions.sum(-1))
        for row, (t, pos, token, req) in enumerate(reqmap):
            r = {
                "token_scores": per_token[row, : bb.lens[row]],
                "delta": float(res.delta[row]),
                "f_x": float(res.f_x[row]),
                "f_baseline": float(res.f_baseline[row]),
                "bucket": bb.bucket,
                "degraded": False,
                "raw_token_scores": per_token[row],
            }
            self._cache_result(req, r)
            self._deliver(t, pos, token, r)

    def _do_exp_fwd(self, payload) -> None:
        bb, reqmap = payload
        ok, res = self._run_item(
            "exp_fwd", bb, lambda: self.engine._run_bucket_fwd(bb)
        )
        if not ok:
            self.engine.stats.degraded += len(reqmap)
            for (t, pos, token, req) in reqmap:
                self._deliver_degraded(t, pos, token, n_tokens=len(req.tokens))
            return
        # perturbation scores are per POSITION already — no feature axis
        per_token = np.asarray(res.attributions)
        for row, (t, pos, token, req) in enumerate(reqmap):
            r = {
                "token_scores": per_token[row, : bb.lens[row]],
                "delta": float(res.delta[row]),
                "f_x": float(res.f_x[row]),
                "f_baseline": float(res.f_baseline[row]),
                "bucket": bb.bucket,
                "degraded": False,
                "raw_token_scores": per_token[row],
            }
            self._cache_result(req, r)
            self._deliver(t, pos, token, r)

    def _do_exp_start(self, payload) -> None:
        run, reqmap = payload
        ok, _ = self._run_item("exp_start", run, run.start)
        if not ok:
            # rung 0 never ran: there is no partial result to fall back to
            self.engine.stats.degraded += len(reqmap)
            for (t, pos, token, req) in reqmap:
                self._deliver_degraded(t, pos, token, n_tokens=len(req.tokens))
            return
        if run.active:
            self._push(_PRIO_HOP, "hop", payload)
        else:
            self._deliver_run(run, reqmap)

    def _do_hop(self, payload) -> None:
        run, reqmap = payload
        ok, _ = self._run_item("hop", run, run.hop)
        if not ok:
            # the completed rungs stand: degrade ONLY the still-active rows
            run.degrade()
        if run.active:
            self._push(_PRIO_HOP, "hop", payload)
        else:
            self._deliver_run(run, reqmap)

    def _deliver_run(self, run: AdaptiveBucketRun, reqmap) -> None:
        # results arrive in bb.indices order — exactly reqmap's order
        for r, (t, pos, token, req) in zip(run.results(), reqmap):
            r.pop("request", None)
            self._cache_result(req, r)
            self._deliver(t, pos, token, r)

    # -- completion / degradation -------------------------------------------

    def _deliver(self, t: Ticket, pos: int, token: Optional[int], r: dict) -> None:
        r.pop("raw_token_scores", None)
        if t.kind == "explain":
            t.result = r
        else:
            t.attributions.append({"pos": pos, "token": token, **r})
        if r.get("degraded"):
            t.degraded = True
        t._pending_explains -= 1
        self._maybe_finish(t)

    def _deliver_degraded(
        self, t: Ticket, pos: int, token: Optional[int], *, n_tokens: int
    ) -> None:
        """Zero-attribution fallback for a request whose explain work could
        not run at all (fault exhaustion / unservable size)."""
        t.degraded = True
        self._deliver(
            t,
            pos,
            token,
            {
                "token_scores": np.zeros((n_tokens,), np.float32),
                "delta": float("inf"),
                "degraded": True,
                "converged": False,
            },
        )

    def _degrade_ticket(
        self, t: Ticket, *, reason: str, keep_tokens: bool = False
    ) -> None:
        t.degraded = True
        self.engine.stats.degraded += 1
        if t.kind == "generate" and (t.tokens is None or not keep_tokens):
            t.tokens = np.zeros((0,), np.int32)
        t._decode_done = True
        t._pending_explains = 0
        t.status = "degraded"
        t.finished_s = self.time_fn()
        self._record_latency(t)

    def _maybe_finish(self, t: Ticket) -> None:
        if t._decode_done and t._pending_explains <= 0 and t.status not in (
            "done",
            "degraded",
        ):
            self._finish(t)

    def _finish(self, t: Ticket) -> None:
        t.status = "degraded" if t.degraded else "done"
        if t.attributions:
            # bucket interleave may deliver out of emission order; the
            # per-token stream the caller sees is position-ordered
            t.attributions.sort(key=lambda a: a["pos"])
        t.finished_s = self.time_fn()
        self._record_latency(t)

    def _record_latency(self, t: Ticket) -> None:
        self.latencies.setdefault(t.slo.name, []).append(t.latency_s)

    def _run_item(self, kind: str, payload: Any, fn: Callable):
        """One retried, straggler-observed work item. Returns (ok, result);
        ``ok=False`` means the retry policy exhausted — the caller degrades
        the affected requests and the loop keeps serving."""
        t0 = time.perf_counter()
        def attempt():
            if self.fault_hook is not None:
                self.fault_hook(kind, payload)
            return fn()
        try:
            out, ok = self.retry(attempt), True
        except Exception as e:  # noqa: BLE001 — degradation boundary
            warnings.warn(
                f"MixedScheduler: {kind} work item failed after retries, "
                f"degrading its requests: {type(e).__name__}: {e}",
                stacklevel=2,
            )
            out, ok = None, False
        self.monitor.observe(time.perf_counter() - t0)
        return ok, out

    # -- reporting -----------------------------------------------------------

    def latency_summary(self) -> dict:
        """Per-SLO-class p50/p99 (seconds) over completed tickets."""
        out = {}
        for name, vals in self.latencies.items():
            v = np.asarray(vals)
            out[name] = {
                "n": int(v.size),
                "p50_s": float(np.percentile(v, 50)),
                "p99_s": float(np.percentile(v, 99)),
            }
        return out
