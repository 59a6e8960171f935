"""Stage 1 of NUIG: probe the model along the path (paper §III Algorithm).

``n_int + 1`` forward-only passes at interval boundaries measure the change in
target probability per interval — the information-content metric. Probes are
batched across (examples × boundaries) so stage 1 rides the same compiled
forward as everything else (the paper's 0.2–3.2% overhead, §IV).

``run_probe`` is the registry-facing entry point: every schedule family in
``repro.core.schedule.SCHEDULES`` names one of the probe kinds here and the
caller never special-cases a method. ``target`` may be any pytree of
per-example arrays (e.g. ``{"target": ids, "pos": positions}`` for bucketed
serving) — it is repeated along axis 0 to match the folded (batch × probe)
axis. ``mask`` pins padded positions to the baseline so the probe never sees
off-path interpolants for shape-bucketed requests.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.paths import interpolate, mask_to_baseline
from repro.core.schedule import Probe

# f: (xs (N, *F), targets) -> (N,) scalar model output (prob / log-prob).
# ``targets`` is a pytree of (N, ...) arrays; plain (N,) ids are the common case.
ScalarFn = Callable[[jax.Array, Any], jax.Array]


def repeat_tree(target: Any, k: int) -> Any:
    """Repeat every leaf k× along axis 0: (B, ...) -> (B*k, ...)."""
    return jax.tree.map(lambda a: jnp.repeat(a, k, axis=0), target)


def boundary_values(
    f: ScalarFn,
    x: jax.Array,
    baseline: jax.Array,
    target: Any,
    n_int: int,
    *,
    mask: Optional[jax.Array] = None,
    known_fx: Optional[jax.Array] = None,
) -> jax.Array:
    """f at the n_int+1 uniform interval boundaries. Returns (B, n_int+1).

    ``known_fx`` is the KV-cache probe-reuse contract (unified serving,
    DESIGN.md §11): the α=1 boundary IS ``f(x)``, and a decode path that
    already ran the prompt forward (prefill logits) can hand that value in
    instead of paying the forward again — only the n_int boundaries below 1
    are evaluated and the passed (B,) value is spliced into the last slot.
    Per-row forward values are batch-shape independent, so the spliced probe
    is bit-identical to the full one whenever ``known_fx`` is (which holds
    for f32 prefill logits; see benchmarks/mixed_serving.py's gate).
    """
    B = x.shape[0]
    x = mask_to_baseline(x, baseline, mask)
    if known_fx is None:
        alphas = jnp.arange(n_int + 1) / n_int
        xi = interpolate(x, baseline, alphas)  # (B, n+1, *F)
        flat = xi.reshape((B * (n_int + 1),) + x.shape[1:])
        t = repeat_tree(target, n_int + 1)
        return f(flat, t).reshape(B, n_int + 1)
    alphas = jnp.arange(n_int) / n_int  # boundaries below α=1 only
    xi = interpolate(x, baseline, alphas)
    flat = xi.reshape((B * n_int,) + x.shape[1:])
    t = repeat_tree(target, n_int)
    vals = f(flat, t).reshape(B, n_int)
    return jnp.concatenate([vals, known_fx.astype(vals.dtype)[:, None]], axis=1)


def refined_boundaries(
    f: ScalarFn,
    x: jax.Array,
    baseline: jax.Array,
    target: Any,
    n0: int,
    rounds: int,
    *,
    mask: Optional[jax.Array] = None,
    known_fx: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Beyond-paper `secant-refine`: adaptively bisect the largest-|Δf|
    interval, one probe per round (static shapes: capacity = n0+1+rounds).

    Returns (boundaries (B, K), values (B, K)) sorted by boundary; padding
    duplicates the rightmost boundary (zero-width intervals, zero Δf).
    ``known_fx`` seeds the α=1 boundary value (see ``boundary_values``);
    bisection rounds never revisit the endpoints, so the splice is exact.
    """
    B = x.shape[0]
    x = mask_to_baseline(x, baseline, mask)
    vals0 = boundary_values(f, x, baseline, target, n0, known_fx=known_fx)
    b0 = jnp.broadcast_to(jnp.arange(n0 + 1) / n0, (B, n0 + 1))
    pad = rounds
    b = jnp.concatenate([b0, jnp.ones((B, pad))], axis=1)
    v = jnp.concatenate([vals0, jnp.repeat(vals0[:, -1:], pad, axis=1)], axis=1)

    def round_step(carry, _):
        b, v = carry
        d = jnp.abs(jnp.diff(v, axis=1)) * (jnp.diff(b, axis=1) > 1e-9)
        i = jnp.argmax(d, axis=1)  # (B,) interval to bisect
        left = jnp.take_along_axis(b, i[:, None], 1)[:, 0]
        right = jnp.take_along_axis(b, i[:, None] + 1, 1)[:, 0]
        mid = 0.5 * (left + right)
        xm = baseline + mid.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype) * (x - baseline)
        fm = f(xm, target)  # one batched probe per round
        # replace one padding slot (rightmost duplicate) with the new point
        slot = b.shape[1] - 1
        b2 = b.at[:, slot].set(mid)
        v2 = v.at[:, slot].set(fm)
        order = jnp.argsort(b2, axis=1)
        return (jnp.take_along_axis(b2, order, 1), jnp.take_along_axis(v2, order, 1)), None

    (b, v), _ = jax.lax.scan(round_step, (b, v), None, length=rounds)
    return b, v


def probe_cost(
    kind: str, *, n_int: int = 4, rounds: int = 4, known_fx: bool = False
) -> int:
    """Forward passes a probe kind spends per example (0 gradient steps).

    The adaptive serving path reports steps-to-tolerance; probe forwards are
    the paper's 0.2–3.2% stage-1 overhead and are accounted separately from
    gradient steps (a forward is roughly a third of a forward+backward).
    ``known_fx`` is the probe-reuse contract: the α=1 forward is donated by
    the decode path, so probing pays one fewer forward per example.
    """
    if kind == "none":
        return 0
    if kind == "boundary":
        base = n_int + 1
    elif kind == "refine":
        base = n_int + 1 + rounds
    else:
        raise ValueError(f"unknown probe kind {kind!r}")
    return base - 1 if known_fx else base


@jax.named_scope("repro.probe")  # the name device traces show
def run_probe(
    kind: str,
    f: ScalarFn,
    x: jax.Array,
    baseline: jax.Array,
    target: Any,
    *,
    n_int: int = 4,
    rounds: int = 4,
    mask: Optional[jax.Array] = None,
    known_fx: Optional[jax.Array] = None,
) -> Optional[Probe]:
    """Run the stage-1 probe a schedule family declares. Uniform signature
    for every kind so registries/engines need no per-method branching.
    ``known_fx`` (B,) donates the α=1 endpoint value (probe-reuse contract —
    see ``boundary_values``); ignored by probe kind "none"."""
    if kind == "none":
        return None
    if kind == "boundary":
        vals = boundary_values(f, x, baseline, target, n_int, mask=mask,
                               known_fx=known_fx)
        bounds = jnp.broadcast_to(jnp.arange(n_int + 1) / n_int, vals.shape)
        return Probe(bounds.astype(jnp.float32), vals)
    if kind == "refine":
        b, v = refined_boundaries(f, x, baseline, target, n_int, rounds,
                                  mask=mask, known_fx=known_fx)
        return Probe(b, v)
    raise ValueError(f"unknown probe kind {kind!r}")
