"""Interpolation schedules — the paper's contribution lives here.

A *schedule* is a pair ``(alphas[m], weights[m])`` approximating
``∫_0^1 g(α) dα ≈ Σ_k w_k g(α_k)``. Schedules are **data, not shapes**: the
same compiled stage-2 executable serves any allocation (the TPU-native
re-design of the paper's per-image dynamic step distribution; DESIGN.md §2).

Schedules:
  uniform        — baseline IG (left/right/midpoint/trapezoid Riemann)
  paper          — faithful NUIG: n_int equal intervals, integer step counts
                   ∝ sqrt(|Δf|) (largest-remainder rounding), uniform-in-interval
  warp           — beyond-paper: continuous inverse-CDF limit of `paper`
  gauss          — beyond-paper: Gauss–Legendre nodes in the warped domain
All functions are jit-compatible and batched over examples where noted.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Schedule(NamedTuple):
    alphas: jax.Array  # (m,) or (B, m) — path positions in [0, 1]
    weights: jax.Array  # same shape — Riemann/quadrature weights, sum == 1


# ----------------------------------------------------------------- uniform


def uniform(m: int, rule: str = "midpoint") -> Schedule:
    """Baseline IG discretization (paper Eq. 2 uses the 'right'/'left' form).

    Args:
        m: node count; rule: "midpoint" | "left" | "right" | "trapezoid".

    Returns a ``Schedule`` with Σw == 1 for every rule and m:

        >>> s = uniform(4)
        >>> [round(float(a), 3) for a in s.alphas]
        [0.125, 0.375, 0.625, 0.875]
        >>> float(s.weights.sum())
        1.0
    """
    if rule == "midpoint":
        a = (jnp.arange(m) + 0.5) / m
        w = jnp.full((m,), 1.0 / m)
    elif rule == "left":
        a = jnp.arange(m) / m
        w = jnp.full((m,), 1.0 / m)
    elif rule == "right":
        a = jnp.arange(1, m + 1) / m
        w = jnp.full((m,), 1.0 / m)
    elif rule == "trapezoid":
        if m == 1:
            # Degenerate trapezoid: a single node IS both endpoints, and
            # halving "each" endpoint would hit the same slot twice (the
            # historical Σw == 0.25 bug). One node integrating [0, 1] must
            # carry the full measure; the midpoint is its unbiased position.
            a = jnp.asarray([0.5])
            w = jnp.asarray([1.0])
        else:
            a = jnp.arange(m) / (m - 1)
            w = jnp.full((m,), 1.0 / (m - 1))
            w = w.at[0].mul(0.5).at[-1].mul(0.5)
    else:
        raise ValueError(f"unknown rule {rule!r}")
    return Schedule(a.astype(jnp.float32), w.astype(jnp.float32))


# ------------------------------------------------- paper step allocation


def normalized_deltas(boundary_vals: jax.Array, power: float = 0.5) -> jax.Array:
    """|Δf| per interval -> importance density, normalized to sum 1.

    boundary_vals: (..., n_int+1) stage-1 probe outputs f(x(α_i)).
    ``power=0.5`` is the paper's sqrt attenuation (§III Algorithm).
    """
    d = jnp.abs(jnp.diff(boundary_vals, axis=-1))  # (..., n_int)
    d = d ** power
    # flat-region fallback: if all deltas vanish, fall back to uniform
    s = d.sum(-1, keepdims=True)
    n = d.shape[-1]
    return jnp.where(s > 1e-12, d / jnp.maximum(s, 1e-12), 1.0 / n)


def allocate_steps(importance: jax.Array, m: int, min_steps: int = 1) -> jax.Array:
    """Integer largest-remainder allocation of m steps ∝ importance.

    importance: (..., n_int) normalized;  returns int32 (..., n_int), sum == m.
    ``min_steps`` guards the paper's n_int>8 pathology (starved intervals).
    """
    n = importance.shape[-1]
    assert m >= n * min_steps, (m, n, min_steps)
    budget = m - n * min_steps
    q = importance * budget
    base = jnp.floor(q).astype(jnp.int32)
    rem = q - base
    short = budget - base.sum(-1, keepdims=True)  # how many +1s to hand out
    # rank remainders descending; slots with rank < short get +1
    order = jnp.argsort(-rem, axis=-1)
    rank = jnp.argsort(order, axis=-1)
    bonus = (rank < short).astype(jnp.int32)
    return base + bonus + min_steps


def from_allocation(
    alloc: jax.Array, m: int, lo: float = 0.0, hi: float = 1.0, rule: str = "midpoint"
) -> Schedule:
    """Uniform-in-interval schedule from integer per-interval step counts.

    alloc: (..., n_int) int32 summing to m. Fully static-shape: step k is
    mapped to its interval by a searchsorted-style comparison — the gather
    trick that makes the paper's dynamic allocation compile once on TPU.
    """
    n = alloc.shape[-1]
    csum = jnp.cumsum(alloc, axis=-1)  # (..., n)
    k = jnp.arange(m)  # (m,)
    # interval of step k: first i with csum[i] > k
    iv = (k[..., None, :] >= csum[..., :, None]).sum(-2)  # (..., m) int
    starts = csum - alloc  # first step index of each interval
    take = lambda t: jnp.take_along_axis(t, iv, axis=-1)
    m_i = take(alloc)  # steps in k's interval
    r = k - take(starts)  # rank of k within its interval
    width = (hi - lo) / n
    off = {"midpoint": 0.5, "left": 0.0, "right": 1.0}[rule]
    a = lo + (iv + (r + off) / m_i) * width
    w = width / m_i
    return Schedule(a.astype(jnp.float32), w.astype(jnp.float32))


def paper(
    boundary_vals: jax.Array,
    m: int,
    *,
    power: float = 0.5,
    min_steps: int = 1,
    rule: str = "midpoint",
) -> Schedule:
    """Faithful NUIG schedule from stage-1 probe values (paper §III)."""
    imp = normalized_deltas(boundary_vals, power)
    alloc = allocate_steps(imp, m, min_steps)
    return from_allocation(alloc, m, rule=rule)


# ----------------------------------------------------------- warp (beyond)


def warp(boundary_vals: jax.Array, m: int, *, power: float = 0.5) -> Schedule:
    """Continuous limit of `paper`: α_k = G⁻¹((k+½)/m) with piecewise-linear
    CDF G whose density on interval i is ∝ |Δf_i|^power.

    Removes integer-rounding pathologies (the paper's n_int>8 regression) and
    keeps weights piecewise-constant-in-interval — so it IS the paper's scheme
    with fractional step counts.

    A density floor (blend with uniform, λ = n/m) is the continuous analogue
    of the paper's ``min_steps=1``: it guarantees every interval's CDF span
    is ≥ 1/m, hence receives ≥ 1 of the m grid points, hence Σw == 1 exactly
    (a zero-density interval would otherwise be silently dropped from the
    quadrature — unbounded error if f moves there).
    """
    imp = normalized_deltas(boundary_vals, power)  # (..., n)
    n = imp.shape[-1]
    lam = min(1.0, n / m)
    imp = (1.0 - lam) * imp + lam / n
    cdf = jnp.cumsum(imp, axis=-1)  # G at right boundaries
    t = (jnp.arange(m) + 0.5) / m  # (m,)
    iv = (t[..., None, :] >= cdf[..., :, None]).sum(-2)  # (..., m)
    iv = jnp.clip(iv, 0, n - 1)
    take = lambda v: jnp.take_along_axis(v, iv, axis=-1)
    left_cdf = take(cdf - imp)
    dens = take(imp)  # mass of k's interval
    frac = (t - left_cdf) / jnp.maximum(dens, 1e-12)
    a = (iv + frac) / n  # sorted inverse-CDF nodes
    # Voronoi-cell weights: w_k = (midpoint to next node) − (midpoint to
    # previous node), with 0/1 at the ends. Telescopes to Σw == 1 exactly and
    # is second-order on smooth integrands — per-interval-uniform weights at
    # non-midpoint nodes would degrade to O(1/m).
    mid = 0.5 * (a[..., 1:] + a[..., :-1])
    lo = jnp.concatenate([jnp.zeros_like(a[..., :1]), mid], axis=-1)
    hi = jnp.concatenate([mid, jnp.ones_like(a[..., :1])], axis=-1)
    w = hi - lo
    return Schedule(a.astype(jnp.float32), w.astype(jnp.float32))


# ---------------------------------------------------------- gauss (beyond)


def _gauss_legendre(m: int) -> tuple[np.ndarray, np.ndarray]:
    x, w = np.polynomial.legendre.leggauss(m)  # nodes on [-1,1]
    return (x + 1.0) / 2.0, w / 2.0  # map to [0,1]


def gauss(
    boundary_vals: jax.Array, m: int, *, power: float = 0.5, order: int = 8
) -> Schedule:
    """Composite Gauss–Legendre in the importance-allocated intervals.

    m steps = (m/order) Gauss cells of fixed ``order``; cells are distributed
    across intervals ∝ |Δf|^power (largest remainder, ≥1), sub-cells are equal
    within an interval. A *global* Gauss rule would lose its order at the
    piecewise-linear warp kinks; the composite rule is exact per smooth piece
    (degree 2·order−1). Beyond-paper.
    """
    imp = normalized_deltas(boundary_vals, power)
    n = imp.shape[-1]
    # shrink order if needed so every interval can get >= 1 cell
    order = min(order, m // n)
    while m % order:
        order -= 1
    assert order >= 1, (m, n)
    cells = m // order
    nodes, gw = _gauss_legendre(order)  # static, tiny
    alloc = allocate_steps(imp, cells, min_steps=1)  # cells per interval
    csum = jnp.cumsum(alloc, axis=-1)
    k = jnp.arange(m)
    cell = k // order
    node = k % order
    iv = (cell[..., None, :] >= csum[..., :, None]).sum(-2)  # (..., m)
    starts = csum - alloc
    take = lambda t_: jnp.take_along_axis(t_, iv, axis=-1)
    cells_i = take(alloc)
    r = cell - take(starts)  # sub-cell rank within interval
    width = 1.0 / n
    sub = width / cells_i
    a = (iv * width) + (r + jnp.asarray(nodes, jnp.float32)[node]) * sub
    w = jnp.asarray(gw, jnp.float32)[node] * sub
    return Schedule(a.astype(jnp.float32), w.astype(jnp.float32))


# ------------------------------------------- refined boundaries (beyond)


def from_boundaries(
    bounds: jax.Array, vals: jax.Array, m: int, *, power: float = 0.5
) -> Schedule:
    """Schedule over *non-uniform* interval boundaries (secant-refine stage 1).

    bounds/vals: (..., K) sorted probe positions and f values; zero-width
    (padding) intervals receive zero importance and zero steps.
    """
    widths = jnp.diff(bounds, axis=-1)  # (..., n)
    d = jnp.abs(jnp.diff(vals, axis=-1)) ** power
    d = jnp.where(widths > 1e-9, d, 0.0)
    s = d.sum(-1, keepdims=True)
    live = (widths > 1e-9).astype(jnp.float32)
    imp = jnp.where(s > 1e-12, d / jnp.maximum(s, 1e-12), live / jnp.maximum(live.sum(-1, keepdims=True), 1))
    alloc = allocate_steps(imp, m, min_steps=0)
    csum = jnp.cumsum(alloc, axis=-1)
    k = jnp.arange(m)
    iv = (k[..., None, :] >= csum[..., :, None]).sum(-2)
    starts = csum - alloc
    take = lambda t: jnp.take_along_axis(t, iv, axis=-1)
    m_i = jnp.maximum(take(alloc), 1)
    r = k - take(starts)
    left = take(bounds[..., :-1])
    w_int = take(widths)
    a = left + (r + 0.5) / m_i * w_int
    w = w_int / m_i
    # With min_steps=0 a live interval can receive zero nodes; its width
    # would then be silently dropped from the quadrature (Σw < 1 — a
    # completeness gap that no m can close). Renormalize: a no-op when every
    # live interval got a node, and a uniform rescale (keeping nodes at
    # their sub-interval midpoints) in the starved m < n_live corner.
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-12)
    return Schedule(a.astype(jnp.float32), w.astype(jnp.float32))


# ------------------------------------------- nested refinement (adaptive)


def refine_nested(sched: Schedule) -> Schedule:
    """Double a schedule's node count while keeping every old node — the
    escalation step of adaptive iso-convergence serving (DESIGN.md §7).

    Each node owns a *cell*: sort nodes by α and partition [0, 1] by the
    cumulative weights (for midpoint/paper/warp the weights ARE the path-cell
    widths, so these are the true cells). Split every cell at its center and
    drop one child node at the center of the half the old node does not
    occupy. Old weights halve EXACTLY (power-of-two scaling is exact in
    IEEE-754 away from subnormals), which is the property that makes a
    resumed accumulator bit-identical to a fresh run over the refined
    schedule: ``ig.attribute(state=prior, state_scale=0.5)`` over the new
    nodes equals one fixed-m run over the whole refined schedule.

    Storage order is load-bearing: the refined schedule is
    ``[old nodes (original order), child nodes (parent order)]`` — NOT
    sorted — so a chunked scan over the refined schedule visits exactly the
    prefix an earlier rung already accumulated. Quadrature does not care
    about node order; resumability does.

    Works batched on (..., m) schedules; Σw == 1 is preserved exactly:

        >>> s = uniform(4)
        >>> r = refine_nested(s)
        >>> r.alphas.shape, bool((r.alphas[:4] == s.alphas).all())
        ((8,), True)
        >>> bool((r.weights[:4] == 0.5 * s.weights).all())
        True
    """
    a, w = sched.alphas, sched.weights
    order = jnp.argsort(a, axis=-1)  # stable (jnp default)
    inv = jnp.argsort(order, axis=-1)
    take = lambda t, i: jnp.take_along_axis(t, i, axis=-1)
    a_s, w_s = take(a, order), take(w, order)
    right = jnp.cumsum(w_s, axis=-1)
    left = right - w_s
    center = left + 0.5 * w_s
    # Child placement. Off-center parents (left/right rules, warp tails):
    # reflect through the cell center — the pair's first moment matches the
    # cell's exactly, so the composite rule stays second order. Near-centered
    # parents (midpoint-style schedules) would reflect onto themselves
    # (duplicate node = wasted gradient), so treat adjacent cells as PAIRS:
    # the even cell's child goes β·w left of its center, the odd cell's
    # β·w right. Any symmetric offset matches the pair's first moment;
    # β = (√(5/3) − 1)/2 also matches its second moment (solve
    # d² − wd − w²/6 = 0 for adjacent equal-width cells), giving third-order
    # pair error — measured ~10-40× lower quadrature error than naive
    # half-cell placement, and within ~10× of a fresh midpoint grid.
    beta = jnp.float32((np.sqrt(5.0 / 3.0) - 1.0) / 2.0)
    off = a_s - center
    near = jnp.abs(off) < 0.25 * w_s
    parity = (jnp.arange(a.shape[-1]) % 2) == 0
    pair_child = jnp.where(parity, center - beta * w_s, center + beta * w_s)
    # A parent outside its own cell (a schedule whose nodes do not sit in
    # their weight cells, e.g. secant-refined bounds) would reflect outside
    # [0, 1]; its child is clipped to the cell (and to 1.0, which an f32
    # cumsum of the weights can overshoot by an ulp). In-cell parents are
    # unaffected: their reflection already lies in the cell.
    reflected = jnp.clip(2.0 * center - a_s, left, jnp.minimum(right, 1.0))
    child_s = jnp.where(near, pair_child, reflected)
    child = take(child_s, inv)  # parent-aligned storage order
    a2 = jnp.concatenate([a, child], axis=-1)
    w2 = jnp.concatenate([0.5 * w, 0.5 * w], axis=-1)
    return Schedule(a2.astype(jnp.float32), w2.astype(jnp.float32))


def m_ladder(m: int, m_max: int) -> tuple[int, ...]:
    """Escalation rungs m, 2m, 4m, ... up to (at most) m_max.

        >>> m_ladder(16, 64)
        (16, 32, 64)
        >>> m_ladder(8, 100)  # never overshoots m_max
        (8, 16, 32, 64)
    """
    assert m >= 1 and m_max >= m, (m, m_max)
    out = [m]
    while out[-1] * 2 <= m_max:
        out.append(out[-1] * 2)
    return tuple(out)


# ------------------------------------------------------------------ registry


class Probe(NamedTuple):
    """Stage-1 output, schedule-family agnostic.

    bounds: (..., K) sorted probe positions in [0, 1];
    vals:   (..., K) f at those positions.
    For the plain boundary probe the bounds are the uniform grid; the
    secant-refine probe returns non-uniform (possibly duplicated) bounds.
    """

    bounds: jax.Array
    vals: jax.Array


@dataclass(frozen=True)
class ScheduleFamily:
    """One schedule family = a probe spec + a uniform-signature builder.

    ``probe`` names the stage-1 pass the caller must run ("none" |
    "boundary" | "refine" — see ``repro.core.probes.run_probe``); ``build``
    maps its result to a Schedule. Every family rides the same call shape,
    so engines dispatch by name with no per-method special cases
    (``refine`` included — DESIGN.md §2).

    ``refine`` is the family's nested-refinement step for adaptive serving
    (DESIGN.md §7): ``refine(sched) -> sched'`` doubles the node count while
    reusing the prior grid, so ladder escalation never discards work. The
    generic cell-splitting ``refine_nested`` is correct for every family
    (Σw == 1; old nodes kept with exactly-halved weights); families with a
    sharper nested rule can override it.
    """

    name: str
    probe: str  # "none" | "boundary" | "refine"
    build: Callable[..., Schedule]
    refine: Callable[[Schedule], Schedule] = refine_nested


def _build_uniform(
    probe: Optional[Probe], m: int, *, power: float, min_steps: int, rule: str
) -> Schedule:
    return uniform(m, rule)


def _build_paper(
    probe: Optional[Probe], m: int, *, power: float, min_steps: int, rule: str
) -> Schedule:
    return paper(probe.vals, m, power=power, min_steps=min_steps, rule=rule)


def _build_warp(
    probe: Optional[Probe], m: int, *, power: float, min_steps: int, rule: str
) -> Schedule:
    return warp(probe.vals, m, power=power)


def _build_gauss(
    probe: Optional[Probe], m: int, *, power: float, min_steps: int, rule: str
) -> Schedule:
    return gauss(probe.vals, m, power=power)


def _build_refine(
    probe: Optional[Probe], m: int, *, power: float, min_steps: int, rule: str
) -> Schedule:
    return from_boundaries(probe.bounds, probe.vals, m, power=power)


SCHEDULES: dict[str, ScheduleFamily] = {
    "uniform": ScheduleFamily("uniform", "none", _build_uniform),
    "paper": ScheduleFamily("paper", "boundary", _build_paper),
    "warp": ScheduleFamily("warp", "boundary", _build_warp),
    "gauss": ScheduleFamily("gauss", "boundary", _build_gauss),
    "refine": ScheduleFamily("refine", "refine", _build_refine),
}


def family(name: str) -> ScheduleFamily:
    """Look up a registered ``ScheduleFamily`` by name.

        >>> sorted(SCHEDULES)
        ['gauss', 'paper', 'refine', 'uniform', 'warp']
        >>> family("paper").probe
        'boundary'
    """
    if name not in SCHEDULES:
        raise ValueError(f"unknown method {name!r}; known: {sorted(SCHEDULES)}")
    return SCHEDULES[name]
