"""Set a cell up, drive it for the window, and record every request.

Set-up makes the weights on the device from the seed, builds one
``ExplainEngine`` with the configuration's settings and the mix's
explanation, puts one ``MixedScheduler`` in front of it, makes the inputs,
and warms every executable shape the mix can produce (and the host-side
programs those shapes run through).

The window then drives ``MixedScheduler.submit`` and ``step`` with the
harness's own clock: an open-loop request is submitted once it is due and
timed from when it was due; a closed-loop one is sent as another finishes.
Requests due in an open-loop window are waited for after it closes (up to
``DRAIN_S``); one that never finishes, or is refused or degraded, counts
as infinitely late.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import math
import time
from typing import Any, Optional

import jax

from bench.harness import traffic as tr

DRAIN_S = 60.0
DONE = ("done", "degraded", "rejected_backpressure", "rejected_rate")


@dataclasses.dataclass
class Rec:
    """One request of the window. Times are seconds from the window's start."""

    index: int  # into the input pool
    due: float
    submit: float
    ticket: Any
    finish: float = math.inf

    @property
    def ok(self) -> bool:
        return self.ticket.status == "done" and math.isfinite(self.finish)

    @property
    def latency(self) -> float:
        return self.finish - self.due if self.ok else math.inf


@dataclasses.dataclass
class Window:
    seconds: float  # the length asked for
    elapsed: float  # measured: ends with the step that crossed ``seconds``
    records: list
    stats_before: Any
    stats_after: Any
    misses: int  # executable-cache compiles inside the window
    backend_compiles: int  # every XLA compile inside the window
    steps: int
    step_max_s: float = 0.0  # the longest scheduler step
    gc_pauses: list = dataclasses.field(default_factory=list)  # seconds, each collection


class GcPauses:
    """Times every garbage collection inside a ``with`` block."""

    def __enter__(self) -> "GcPauses":
        self.pauses: list = []
        gc.callbacks.append(self._event)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


class Compiles:
    """Counts XLA backend compiles inside a ``with`` block."""

    def __enter__(self) -> "Compiles":
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, name: str, *args, **kwargs) -> None:
        if name.endswith("backend_compile_duration"):
            self.n += 1


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class CellRun:
    """One cell: ``setup``, then ``window``, then ``free`` before the
    reference runs."""

    def __init__(self, info: dict, seed: int, seconds: float, clock=time.perf_counter):
        self.info = info
        self.sizes = info["sizes"]
        self.model = info["model"]
        self.traffic = info["traffic"]
        self.seed = seed
        self.seconds = seconds
        self.clock = clock

    # ---------------------------------------------------------------- set-up

    def setup(self) -> None:
        from repro.serve import ExplainEngine, MixedScheduler

        c, knobs = self.sizes, self.sizes["engine"]
        params, warm = self._seeded(self.seed, extra=max(knobs["batch_buckets"]))
        self.cfg = self.model.program_config(c)
        self.engine = ExplainEngine(
            self.cfg, params,
            chunk=knobs["chunk"], max_batch=knobs["max_batch"],
            batch_buckets=tuple(knobs["batch_buckets"]),
            seq_buckets=tuple(knobs["seq_buckets"]),
            **tr.engine_kwargs(self.traffic),
        )
        self.sched = MixedScheduler(self.engine, max_queue=knobs["max_queue"],
                                    time_fn=self.clock)
        self._warm(warm)
        self._settle()

    def reseed(self, seed: int) -> None:
        """New weights and traffic from another seed on the same engine (its
        executables take the weights as an argument): many seeds' readings
        in one process."""
        self.seed = seed
        self._seeded(seed, extra=0)
        self.engine.params = self.params
        self._settle()

    @staticmethod
    def _settle() -> None:
        """Collect, then move what set-up made out of the collector's sight,
        so a collection inside the window scans only what the window makes."""
        gc.collect()
        gc.freeze()

    def _seeded(self, seed: int, extra: int) -> tuple:
        """Weights, arrivals and requests from the seed; returns the weights
        and ``extra`` further requests for the warm-up."""
        from repro.serve import ExplainRequest

        c, t = self.sizes, self.traffic
        self.rng, key_int = tr.rngs(seed)
        key = jax.random.PRNGKey(key_int)
        self.params = self.model.init_params(c, jax.random.fold_in(key, 0))
        if t["arrivals"] == "open":
            self.due = tr.arrivals(t, self.seconds)
            n = len(self.due)
        else:
            self.due = None
            n = tr.pool_size(t, self.seconds)
        inputs = self.model.make_inputs(c, self.params, t, jax.random.fold_in(key, 1),
                                        self.rng, n + extra)
        self.inputs = inputs[:n]
        reqs = [ExplainRequest(tokens=i["tokens"], target=i["target"], features=i["features"])
                for i in inputs]
        self.requests = reqs[:n]
        return self.params, reqs[n:]

    def _warm(self, pool: list) -> None:
        """Serve one batch of every size on the batch ladder. An adaptive
        engine serves them with its tolerance at 0, so every row climbs the
        whole ladder and every (batch, rung) hop executable is built; the
        schedule refinement each hop runs on the host is warmed for every
        survivor count."""
        eng = self.engine
        tol, eng.tol = eng.tol, 0.0
        try:
            for b in eng.batch_buckets:
                tickets = [self.sched.submit(r) for r in pool[:b]]
                self.sched.run_until_idle()
                bad = [tk.status for tk in tickets if tk.status != "done"]
                if bad:
                    raise RuntimeError(f"warm-up batch of {b}: tickets {bad}")
        finally:
            eng.tol = tol
        if eng.adaptive:
            import jax.numpy as jnp

            from repro.core.schedule import Schedule, family

            refine = family(eng.schedule).refine
            for k in range(1, max(eng.batch_buckets) + 1):
                for m in eng.m_ladder[:-1]:
                    s = Schedule(jnp.full((k, m), 0.5, jnp.float32),
                                 jnp.full((k, m), 1.0 / m, jnp.float32))
                    jax.block_until_ready(refine(s))

    # ---------------------------------------------------------------- window

    def window(self) -> Window:
        gc.collect()
        with Compiles() as compiles, GcPauses() as pauses:
            win = self._window()
        win.backend_compiles = compiles.n
        win.gc_pauses = pauses.pauses
        return win

    def _window(self) -> Window:
        eng, sched, clock = self.engine, self.sched, self.clock
        before = copy.deepcopy(eng.stats)
        misses0 = eng.stats.misses
        recs: list[Rec] = []
        live = self._live = []
        steps, step_max = 0, 0.0
        T = self.seconds
        t0 = self._t0 = clock()
        now = 0.0

        def submit(i: int, due: float) -> None:
            with span("bench.submit"):
                tk = sched.submit(self.requests[i % len(self.requests)])
            r = Rec(i, due, clock() - t0, tk)
            recs.append(r)
            if tk.status in DONE:  # refused at the door
                r.finish = math.inf
            else:
                live.append(r)

        if self.due is None:
            for i in range(int(self.traffic["outstanding"])):
                submit(i, 0.0)
        nxt = len(recs)
        while now < T:
            if self.due is not None:
                while nxt < len(self.due) and self.due[nxt] <= now:
                    submit(nxt, float(self.due[nxt]))
                    nxt += 1
            t_step = clock()
            with span("bench.step"):
                worked = sched.step()
            steps += worked
            step_max = max(step_max, clock() - t_step)
            now = clock() - t0
            for _ in self._reap(now):
                if self.due is None and now < T:
                    submit(nxt, now)
                    nxt += 1
            if not worked and self.due is not None:
                wake = min(float(self.due[nxt]) if nxt < len(self.due) else T, T)
                with span("bench.wait"):
                    while (now := clock() - t0) < wake:
                        time.sleep(min(wake - now, 0.002))
            now = clock() - t0
        elapsed = now
        if self.due is not None:  # due before the close, not yet sent: late
            while nxt < len(self.due) and self.due[nxt] < T:
                submit(nxt, float(self.due[nxt]))
                nxt += 1
        after = copy.deepcopy(eng.stats)
        return Window(T, elapsed, recs, before, after, after.misses - misses0, 0, steps,
                      step_max)

    def _reap(self, now: float) -> list:
        """Stamp and drop the requests that finished by ``now``."""
        done = [r for r in self._live if r.ticket.status in DONE]
        for r in done:
            self._live.remove(r)
            r.finish = now if r.ticket.status == "done" else math.inf
        return done

    @property
    def live(self) -> list:
        """The requests sent and not yet finished."""
        return getattr(self, "_live", [])

    def drain(self, win: Window) -> None:
        """Wait for the open-loop requests that fell due in the window, up to
        ``DRAIN_S`` past its close."""
        if self.due is None:
            return
        while self._live and self.clock() - self._t0 < win.elapsed + DRAIN_S:
            self.sched.step()
            self._reap(self.clock() - self._t0)

    # ---------------------------------------------------------------- after

    def results(self, win: Window) -> list:
        """(record, input, result) of every request answered in the window
        (open loop: due in it)."""
        return [(r, self.inputs[r.index % len(self.inputs)], r.ticket.result)
                for r in win.records
                if r.ok and (self.due is not None or r.finish <= win.elapsed)]

    def free(self) -> None:
        """Drop the program's engine, scheduler and executables."""
        for name in ("sched", "engine", "requests"):
            if hasattr(self, name):
                delattr(self, name)
        gc.unfreeze()
        gc.collect()


def memory_peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None
