"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program's engine between set-up and the
window, and the rest of the run (window, sample, reference check) goes as
usual, on the CPU at a small size. The mixes are closed loops with two
full batches in flight, so every batch has rows in both halves. (The
cells run on one chip, so there is no exchange between chips to leave
out.)"""
import numpy as np
import pytest

from bench.harness import loop, runner
from bench.tests import small

SEED = 2**31 + 11


def altered(engine):
    """An answer altered where it is produced: every row's scores in reverse
    position order (a layout mix-up in the program's output)."""
    import jax.numpy as jnp

    orig = engine._timed_call

    def call(bs, ex, args):
        out = orig(bs, ex, args)
        shift = lambda r: r._replace(attributions=jnp.flip(r.attributions, axis=1)) \
            if hasattr(r, "attributions") else r
        return tuple(map(shift, out)) if not hasattr(out, "attributions") else shift(out)

    engine._timed_call = call


def half_batch(engine):
    """Half of each batch left out: its rows get the other half's answers."""
    import jax

    orig = engine._timed_call

    def call(bs, ex, args):
        out = orig(bs, ex, args)
        B = args[0].shape[0]
        if B < 2:
            return out
        sel = np.arange(B) % (B // 2)
        return jax.tree.map(lambda a: a[sel] if getattr(a, "ndim", 0) and a.shape[0] == B else a,
                            out)

    engine._timed_call = call


def stale_ladder(engine, monkeypatch):
    """A ladder hop that returns its state unchanged: the rows climb the
    rungs but keep the first rung's answer."""
    from repro.serve.explain_engine import AdaptiveBucketRun

    def hop(self):
        if not self.active:
            return False
        rung = self.eng.m_ladder[self._rung_i]
        self._rung_i += 1
        for r in self.act:
            self.m_used[r] = rung
            self.hops[r] += 1
        return self.active

    monkeypatch.setattr(AdaptiveBucketRun, "hop", hop)


FAULTS = [(cell, fault) for cell in small.CELLS for fault in ("altered", "half_batch")]
FAULTS.append(("vit-s16.ig-adaptive.backlog", "stale_ladder"))


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    window = loop.CellRun.window

    def broken_window(self):
        if fault == "stale_ladder":
            stale_ladder(self.engine, monkeypatch)
        else:
            {"altered": altered, "half_batch": half_batch}[fault](self.engine)
        return window(self)

    monkeypatch.setattr(loop.CellRun, "window", broken_window)
    # every answer of the window is compared, so a fault that spares some
    # rows cannot hide behind the sample
    limits = dict(runner.limits_for(name), sample=10_000)
    out = runner.run_cell(name, SEED, 2.0, False, allow_cpu=True, log=lambda s: None,
                          limits=limits, **small.cell(name, closed=True))
    assert out["attempted"] > 0
    assert out["correct"] is False, out["checks"]
