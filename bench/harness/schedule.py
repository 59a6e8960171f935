"""The paper's interpolation schedule and its nested refinement, in numpy.

A plain float64 statement of what the explanation's quadrature nodes are:

* stage 1 probes f at the ``n_int + 1`` interval boundaries ``i / n_int``;
* ``paper_alloc``: interval i gets ``min_steps`` plus a share of the rest
  of the m steps proportional to ``|f_{i+1} - f_i| ** power``, rounded by
  largest remainder (ties to the lower interval);
* ``from_alloc``: the steps of an interval sit at the midpoints of equal
  sub-cells, each weighted by its cell's width;
* ``refine``: the adaptive ladder doubles m by splitting every node's cell;
  old nodes keep their place with half their weight and a child node goes
  into the other half of the cell (a near-centred parent's child goes
  beta * width left of the centre for even-ranked cells, right for odd,
  beta = (sqrt(5/3) - 1) / 2; an off-centre parent's child is its
  reflection through the centre).
"""
from __future__ import annotations

import itertools

import numpy as np

BETA = (np.sqrt(5.0 / 3.0) - 1.0) / 2.0


def paper_alloc(vals: np.ndarray, m: int, power: float = 0.5, min_steps: int = 1) -> np.ndarray:
    d = np.abs(np.diff(np.asarray(vals, np.float64))) ** power
    n = d.size
    s = d.sum()
    imp = d / s if s > 1e-12 else np.full(n, 1.0 / n)
    budget = m - n * min_steps
    q = imp * budget
    base = np.floor(q).astype(np.int64)
    rem = q - base
    short = budget - int(base.sum())
    order = np.argsort(-rem, kind="stable")
    bonus = np.zeros(n, np.int64)
    bonus[order[:short]] = 1
    return base + bonus + min_steps


def from_alloc(alloc: np.ndarray, m: int) -> tuple[np.ndarray, np.ndarray]:
    n = alloc.size
    a, w = np.empty(m), np.empty(m)
    k = 0
    for i, mi in enumerate(alloc):
        r = np.arange(mi)
        a[k:k + mi] = (i + (r + 0.5) / mi) / n
        w[k:k + mi] = 1.0 / (n * mi)
        k += mi
    return a, w


def refine(a: np.ndarray, w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    dt = a.dtype.type
    order = np.argsort(a, kind="stable")
    a_s, w_s = a[order], w[order]
    right = np.cumsum(w_s, dtype=a.dtype)
    left = right - w_s
    center = left + dt(0.5) * w_s
    near = np.abs(a_s - center) < dt(0.25) * w_s
    even = np.arange(a.size) % 2 == 0
    beta = dt(BETA)
    pair = np.where(even, center - beta * w_s, center + beta * w_s)
    reflected = np.clip(dt(2.0) * center - a_s, left, np.minimum(right, dt(1.0)))
    child = np.empty_like(a)
    child[order] = np.where(near, pair, reflected)
    return np.concatenate([a, child]), np.concatenate([dt(0.5) * w, dt(0.5) * w])


def rung(alloc: np.ndarray, m0: int, m: int, dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
    """Nodes and weights at ladder rung ``m`` (m0 doubled until m), worked
    out in ``dtype``. Refinement puts some children exactly on a cell's
    edge, where a neighbour's child may land too, so which side of a tie a
    node falls on at rung 64 and above can turn on the last bit of the
    arithmetic: float32 and float64 give different node sets for some
    allocations."""
    a, w = from_alloc(alloc, m0)
    a, w = a.astype(dtype), w.astype(dtype)
    while a.size < m:
        a, w = refine(a, w)
        a, w = a.astype(dtype), w.astype(dtype)
    if a.size != m:
        raise ValueError(f"rung {m} is not m0={m0} doubled")
    return a.astype(np.float64), w.astype(np.float64)


# the first rung at which float32 and float64 refinement can differ
TIE_RUNG = 64


def variants(alloc: np.ndarray, m0: int, m: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """The distinct node sets of rung ``m`` in float64 and in float32."""
    out = []
    for dt in (np.float64, np.float32):
        a, w = rung(alloc, m0, m, dt)
        if not any(np.allclose(a, b, rtol=0, atol=1e-6) for b, _ in out):
            out.append((a, w))
    return out


def candidate_allocs(vals: np.ndarray, m: int, slack: float, limit: int = 8) -> list[np.ndarray]:
    """The allocations of probe values within ``slack`` of ``vals``, nearest
    first: that of ``vals``, then those of every -/0/+ ``slack`` shift of
    its entries. An interval whose |f| change is small moves a step on
    rounding alone (the allocation goes with its square root)."""
    vals = np.asarray(vals, np.float64)
    shifts = sorted(itertools.product((-1.0, 0.0, 1.0), repeat=vals.size),
                    key=lambda s: sum(map(abs, s)))
    out: list[np.ndarray] = []
    for s in shifts:
        alloc = paper_alloc(vals + slack * np.asarray(s), m)
        if not any(np.array_equal(alloc, o) for o in out):
            out.append(alloc)
            if len(out) == limit:
                break
    return out
