"""Percentiles, due-time latencies and the readers' arithmetic, on records
made by hand."""
import math
from types import SimpleNamespace

import pytest

from bench.harness import loop, spec
from bench.harness.measure import Ctx, nearest_rank


def test_nearest_rank():
    v = list(range(1, 101))  # 1..100
    assert nearest_rank(v, 0.50) == 50
    assert nearest_rank(v, 0.95) == 95
    assert nearest_rank(v, 0.99) == 99
    assert nearest_rank([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        nearest_rank([], 0.5)


def test_failed_requests_sort_last():
    v = [0.1] * 94 + [math.inf] * 6  # 6% infinitely late
    assert nearest_rank(v, 0.50) == 0.1
    assert math.isinf(nearest_rank(v, 0.95))
    assert nearest_rank([0.1] * 95 + [math.inf] * 5, 0.95) == 0.1


def rec(due, submit, finish, status="done", result=None):
    r = loop.Rec(0, due, submit, SimpleNamespace(status=status, result=result))
    r.finish = finish
    return r


def ctx_of(records, elapsed, traffic=None):
    win = loop.Window(elapsed, elapsed, records, None, None, 0, 0, 0)
    return Ctx({}, None, traffic or {"arrivals": "open"}, {}, win, 12.5)


def test_latency_is_timed_from_the_due_time():
    # submitted 0.2 s late (the generator waited on a step): the wait counts
    recs = [rec(1.0, 1.2, 1.5), rec(2.0, 2.0, 2.1), rec(3.0, 3.05, 3.3)]
    ctx = ctx_of(recs, 10.0)
    assert spec.metric_reader("latency_p50_ms").read(ctx) == pytest.approx(300.0)
    assert 1e3 * nearest_rank(ctx.latencies_s(), 0.95) == pytest.approx(500.0)
    assert spec.metric_reader("gen_lag_p99_ms").read(ctx) == pytest.approx(200.0)
    assert spec.metric_reader("setup_s").read(ctx) == 12.5


def test_degraded_refused_and_unfinished_requests_are_infinitely_late():
    ok = [rec(i * 0.1, i * 0.1, i * 0.1 + 0.05) for i in range(18)]
    bad = [rec(2.0, 2.0, 2.3, status="degraded"),
           rec(2.1, 2.1, math.inf, status="rejected_backpressure")]
    ctx = ctx_of(ok + bad, 10.0)
    assert spec.metric_reader("latency_p50_ms").read(ctx) == pytest.approx(50.0)
    assert math.isinf(nearest_rank(ctx.latencies_s(), 0.95))
    # more than half of them failed: the median itself is infinitely late
    ctx = ctx_of(ok[:8] + bad * 5, 10.0)
    assert math.isinf(spec.metric_reader("latency_p50_ms").read(ctx))


def test_closed_loop_rate_counts_answers_inside_the_window():
    recs = [rec(0, 0, 0.5 * i) for i in range(1, 9)] + [rec(4.2, 4.2, math.inf, "queued")]
    ctx = ctx_of(recs, 4.0, {"arrivals": "closed"})
    assert spec.metric_reader("explain_per_s").read(ctx) == pytest.approx(8 / 4.0)
    assert spec.metric_reader("latency_p50_ms").read(ctx) is None


def test_outstanding_time_is_the_union_of_waits():
    recs = [rec(0.0, 0.0, 1.0), rec(0.5, 0.5, 1.5), rec(3.0, 3.0, 4.0), rec(9.0, 9.0, 11.0)]
    ctx = ctx_of(recs, 10.0)
    assert ctx.outstanding() == [[0.0, 1.5], [3.0, 4.0], [9.0, 10.0]]
