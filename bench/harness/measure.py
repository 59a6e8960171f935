"""What a metric reader gets: the window's records, the engine's counters
before and after it, the reduced trace, the peaks, and the arithmetic the
readers share (percentiles, explanation FLOPs, outstanding time).

A reader is ``bench/metrics/<name>.py`` with ``read(ctx) -> float | None``;
``None`` means it found nothing to read, and the metric is left out.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Optional

from bench.harness import trace as tr

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def nearest_rank(values: list, q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the values at or below it. Infinite values (requests that
    failed, were refused or never finished) sort last."""
    if not values:
        raise ValueError("no values")
    v = sorted(values)
    return v[max(math.ceil(q * len(v)) - 1, 0)]


def probe_forwards(traffic: dict) -> int:
    """Forwards a gradient explanation spends besides its steps: the
    ``n_int + 1`` boundary probes of the paper schedule (the endpoints among
    them), or the two endpoints a uniform schedule needs for its delta."""
    return 2 if traffic["schedule"] == "uniform" else traffic["n_int"] + 1


@dataclasses.dataclass
class Ctx:
    sizes: dict
    model: Any
    traffic: dict
    peak: dict
    win: Any  # loop.Window
    setup_s: float
    trace: Optional[dict] = None  # trace.reduce() output
    events: Optional[dict] = None  # trace.load() output

    # ------------------------------------------------------------ records

    @property
    def open_loop(self) -> bool:
        return self.traffic["arrivals"] == "open"

    def latencies_s(self) -> list:
        return [r.latency for r in self.win.records]

    def completed(self) -> list:
        """Requests answered inside the window."""
        return [r for r in self.win.records if r.ok and r.finish <= self.win.elapsed]

    def outstanding(self) -> list:
        """[start, end] seconds of the window with at least one request
        due and not yet answered."""
        end = self.win.elapsed
        return tr.merge([[r.due, min(r.finish, end)] for r in self.win.records
                         if r.due < end and min(r.finish, end) > r.due])

    # ------------------------------------------------------------ FLOPs

    def real_len(self, r) -> int:
        return len(r.ticket.result["token_scores"])

    def explanation_flops(self, r) -> float:
        """The work one explanation needs at its real length: the probe's
        (or endpoints') forwards and m_used forward+VJP steps, or the
        occlusion forwards."""
        c, m, t = self.sizes, self.model, self.traffic
        S = self.real_len(r)
        fwd = m.flops_forward(c, S)
        if t["method"] == "occlusion":
            return m.flops_embed(c, S) + (t["n_masks"] + 2) * fwd
        steps = r.ticket.result.get("m_used", t["m"])
        return m.flops_embed(c, S) + probe_forwards(t) * fwd + steps * (fwd + m.flops_vjp(c, S))

    def launched_grad_work(self) -> Optional[tuple[float, int]]:
        """(FLOPs, executable calls) of the gradient programs launched in
        the window, at their launched shapes (batch and sequence padding
        included, recomputation not): the probe's forwards of each start or
        fixed-m call, and every launched step, from the engine's counters."""
        c, m, t = self.sizes, self.model, self.traffic
        if t["method"] == "occlusion":
            return None
        b0, b1 = self.win.stats_before, self.win.stats_after
        calls = {k: v.calls - b0.buckets.get(k, _Zero).calls for k, v in b1.buckets.items()}
        hops = {k: v.calls - b0.hop_buckets.get(k, _Zero).calls for k, v in b1.hop_buckets.items()}
        seqs = {S for (_, S), n in list(calls.items()) + list(hops.items()) if n}
        if len(seqs) != 1:
            return None
        S = seqs.pop()
        fwd, step = m.flops_forward(c, S), m.flops_forward(c, S) + m.flops_vjp(c, S)
        rows = sum(B * n for (B, _), n in calls.items())
        if t.get("adaptive"):
            steps = b1.adaptive.launched_steps - b0.adaptive.launched_steps
        else:
            steps = rows * t["m"]
        flops = rows * probe_forwards(t) * fwd + steps * step
        return flops, sum(calls.values()) + sum(hops.values())

    # ------------------------------------------------------------ trace

    def to_trace_ns(self, t: float) -> float:
        return self.trace["window_ns"][0] + t * 1e9

    def busy_within(self, intervals_s: list) -> float:
        """Device-busy seconds inside the given window intervals (mean over
        devices)."""
        ivs = [[self.to_trace_ns(s), self.to_trace_ns(e)] for s, e in intervals_s]
        busy = self.trace["busy_ns"]
        return sum(tr.length(tr.intersect(b, ivs)) for b in busy.values()) / len(busy) * 1e-9


class _Zero:
    calls = 0
    requests = 0
