"""Named host spans of the serving layer.

``span(stats, name, **tags)`` is a context manager that does two things:

  * it enters ``jax.profiler.TraceAnnotation(name, **tags)``, so the span
    sits on the profiler's host timeline, the clock a device trace is
    aligned to (the tags are formatted only while a trace is active);
  * it adds its wall time to ``stats.spans[name]``, an always-on
    ``(count, seconds)`` pair that includes the spans nested inside it.

The seconds stay on the span (``.seconds``) after it closes, for callers
that charge them to a counter of their own: ``BucketStats.total_s`` and
``compile_s``, and ``MixedScheduler``'s straggler monitor. ``stats`` may be
None for a span that feeds no counter.

Every span name is below, and each is stable: benchmark readers and trace
reductions find the spans by these names.
"""
from __future__ import annotations

import time
from typing import Any, Optional

import jax

SUBMIT = "repro.sched.submit"  # MixedScheduler.submit
STEP = "repro.sched.step"  # one work item of MixedScheduler.step
ITEM = "repro.sched.item"  # its retried run (what the straggler monitor sees)
FLUSH = "repro.sched.flush"  # pending explains planned into bucket items
DELIVER = "repro.sched.deliver"  # results converted, cached, delivered
INPUTS = "repro.engine.inputs"  # a bucket's prep call dispatched; its device time is WAIT's
COMPILE = "repro.engine.compile"  # an executable-cache miss, refusals included
CALL = "repro.engine.call"  # one executable call: dispatch and wait
WAIT = "repro.engine.wait"  # the wait for its result inside CALL
REFINE = "repro.ladder.refine"  # a hop's schedule refinement
GATHER = "repro.ladder.gather"  # survivors re-padded, hop arguments built
READBACK = "repro.ladder.readback"  # results read back, survivors selected
AUTOTUNE = "repro.autotune.call"  # one timed candidate call of the autotuner

NAMES = (SUBMIT, STEP, ITEM, FLUSH, DELIVER, INPUTS, COMPILE, CALL, WAIT, REFINE,
         GATHER, READBACK, AUTOTUNE)


class span:
    """One timed, annotated span (see the module docstring)."""

    __slots__ = ("_stats", "_name", "_ann", "_t0", "seconds")

    def __init__(self, stats: Optional[Any], name: str, **tags):
        self._stats = stats
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name, **tags)
        self.seconds = 0.0

    def tag(self, **tags) -> None:
        """Tags known only inside the span (e.g. whether a compile remats)."""
        self._ann.set_metadata(**tags)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._stats is not None:
            n, s = self._stats.spans.get(self._name, (0, 0.0))
            self._stats.spans[self._name] = (n + 1, s + self.seconds)
