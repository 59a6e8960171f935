"""The IG engine — stage 2: batched, chunked gradient accumulation.

One compiled program serves every schedule (uniform / paper / warp / gauss):
the (alphas, weights) vectors are runtime data. The step axis is folded into
the batch axis (the paper's GPU batching, as a shardable pjit data axis), and
steps are processed in static-size chunks under ``lax.scan`` so the same
executable serves any m and memory stays bounded.

Attribution methods (DESIGN.md §8): the per-chunk accumulator and the
finalizer are method data, dispatched through the ``repro.core.methods``
MethodSpec registry — vanilla Riemann IG and IDGI's gradient-direction
f-difference split ride the identical scan; path-ensemble methods
(noise_tunnel / expected_grad) expand their batch BEFORE this function and
reduce after it, so per-row they ARE the riemann method.

Kernel injection: ``interp_fn`` / ``accum_fn`` default to the pure-jnp
oracles (the method's registered accumulator) and can be swapped for the
Pallas kernels in ``repro.kernels``.

Masking (shape-bucketed serving, DESIGN.md §6): ``mask`` marks real
positions of right-padded inputs. It is threaded through ``interp_fn`` (padded
positions never leave the baseline), the accumulator (padded gradients never
accumulate), the final attribution (exact zeros at padded positions), and the
completeness gap δ (summed over real positions only — which the exact zeros
make the same as summing everything).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import methods as methods_mod
from repro.core.methods import MethodSpec, expand_mask
from repro.core.paths import interp_add, interpolate, mask_to_baseline
from repro.core.probes import ScalarFn, repeat_tree
from repro.core.schedule import Schedule


class IGResult(NamedTuple):
    attributions: jax.Array  # (B, *F)
    f_x: jax.Array  # (B,) model output at the input
    f_baseline: jax.Array  # (B,) model output at the baseline
    delta: jax.Array  # (B,) convergence δ (completeness gap, Eq. 3)


class IGState(NamedTuple):
    """Resumable stage-2 accumulator (adaptive iso-convergence, DESIGN.md §7).

    ``acc`` is the method's running node sum at the rung last run — for
    riemann methods Σ_k w_k g_k (the path integral estimate *before* the
    (x − x′) factor), for IDGI the attribution itself — and ``f_x``/
    ``f_baseline`` are the endpoint forwards, computed once at rung 0 and
    carried so ladder hops never repeat them. Rows may be gathered/re-batched
    freely: every field is per-example. Any registered method's accumulator
    is additive over nodes and degree-1 in the weights (the MethodSpec state
    contract, DESIGN.md §8), so this one pytree serves the whole zoo.
    """

    acc: jax.Array  # (B, *F) float32 running node sum
    f_x: jax.Array  # (B,)
    f_baseline: jax.Array  # (B,)


def attribute(
    f: ScalarFn,
    x: jax.Array,
    baseline: jax.Array,
    sched: Schedule,
    target: Any,
    *,
    method: Union[str, MethodSpec] = "ig",
    mask: Optional[jax.Array] = None,
    chunk: int = 0,
    fused: bool = False,
    interp_fn: Callable = interpolate,
    interp_add_fn: Callable = interp_add,
    accum_fn: Optional[Callable] = None,
    state: Optional[IGState] = None,
    state_scale: float = 1.0,
    return_state: bool = False,
    f_x: Optional[jax.Array] = None,
):
    """Path attribution along the straight line with any schedule + method.

    f: (xs (N, *F), targets) -> (N,);  x/baseline: (B, *F).
    target: pytree of per-example arrays (plain (B,) ids, or e.g.
    {"target": ids, "pos": positions} for bucketed serving).
    sched.alphas/weights: (m,) shared or (B, m) per-example.
    method: a ``repro.core.methods`` registry name or MethodSpec — selects
    the per-chunk accumulator and the finalizer. Path-ensemble expansion
    (noise_tunnel / expected_grad) is the CALLER's job (``core.api``): this
    function computes one path per row.
    mask: optional (B, *L) real-position mask, L a prefix of the feature dims.
    accum_fn: optional accumulator override (Pallas kernel injection); must
    honor the MethodSpec accumulator signature
    ``(acc, grads, weights, *, diff, mask)``.

    Fused stage 2 (``fused=True``, DESIGN.md §10): the interpolants are
    generated INSIDE the differentiated chunk function — interpolation
    composed with the model forward under one VJP — so the (B·chunk, *F)
    interpolant batch is never a program-boundary tensor that must round-trip
    HBM. For ``grad_linear`` accumulator classes (riemann) the chunk's whole
    weighted gradient sum Σ_k w_k g_k is recovered as ONE (B, *F) cotangent
    (the transpose of the step-axis broadcast), so the per-step gradient
    batch never materializes either; quadratic classes (idgi) keep per-step
    gradients but still fuse the interpolation into the backward program.
    ``interp_add_fn`` is the fused path's kernel-injection hook — the
    interp-plus-carry unit (``paths.interp_add`` oracle; Pallas custom-VJP
    drop-in in ``repro.kernels.interp_accum.ops``). The fused and unfused
    paths accumulate in f32 either way and agree to float tolerance (not
    bitwise — the weight multiply rides the VJP seed instead of the
    accumulator); each is separately bit-identical under adaptive resume.

    Probe-reuse (``f_x``, unified serving): a caller that already holds the
    endpoint forward value f(x) for every row — e.g. the decode loop's chosen
    -token log-prob from the very forward being attributed — passes it here
    and only f(baseline) is computed (a B-row batch instead of 2B). Per-row
    forward values are batch-shape independent, so the result is bit-identical
    to the self-computed endpoints whenever the passed value is. Ignored when
    resuming from ``state`` (endpoints already live there).

    Resumability (DESIGN.md §7): pass ``state`` from a prior call to continue
    accumulating — ``sched`` then holds only the NEW nodes, the endpoint
    forwards are reused, and the prior accumulator enters scaled by
    ``state_scale`` (0.5 per nested-refinement doubling: the old nodes'
    weights in the refined schedule are exactly half their old values, and
    power-of-two scaling is exact, so resuming is bit-identical to one fixed
    run over the full refined schedule at the same ``chunk``). With
    ``return_state`` the call returns ``(IGResult, IGState)``.
    """
    spec = methods_mod.get(method)
    if spec.forward_only:
        raise ValueError(
            f"method {spec.name!r} is forward-only (perturbation class); "
            "it never differentiates the model — use "
            "repro.core.perturb.attribute_from_masks / PerturbExplainer"
        )
    if accum_fn is None:
        accum_fn = spec.accum_fn
    B = x.shape[0]
    # pinned view for the endpoint terms; the scan's interpolants are pinned
    # inside interp_fn (mask kwarg) — exactly one select on each path
    xp = mask_to_baseline(x, baseline, mask)
    diff = xp - baseline  # path direction, consumed by direction-aware accums
    alphas, weights = sched.alphas, sched.weights
    if alphas.ndim == 1:
        alphas = jnp.broadcast_to(alphas, (B,) + alphas.shape)
        weights = jnp.broadcast_to(weights, (B,) + weights.shape)
    m = alphas.shape[-1]
    c = chunk if chunk and chunk < m else m
    assert m % c == 0, f"chunk {c} must divide m {m}"
    n_chunks = m // c
    a_ch = alphas.reshape(B, n_chunks, c).swapaxes(0, 1)  # (n_chunks, B, c)
    w_ch = weights.reshape(B, n_chunks, c).swapaxes(0, 1)

    grad_f = jax.grad(lambda xs, t: f(xs, t).sum())
    mkw = {} if mask is None else {"mask": mask}
    feat = x.shape[1:]

    def step(acc, xs):
        a, w = xs  # (B, c)
        xi = interp_fn(x, baseline, a, **mkw)  # (B, c, *F)
        flat = xi.reshape((B * c,) + feat)
        t = repeat_tree(target, c)
        g = grad_f(flat, t).reshape((B, c) + feat)
        return accum_fn(acc, g, w, diff=diff, **mkw), None

    def step_fused_linear(acc, xs):
        # grad-linear accumulators (riemann class): Σ_k w_k g_k for the whole
        # chunk is the cotangent of a (B, *F) carry broadcast over the step
        # axis — one VJP output, no (B, c, *F) gradient batch, interpolants
        # generated inside the differentiated program (DESIGN.md §10).
        a, w = xs  # (B, c)

        def chunk_sum(u):
            xi = interp_add_fn(x, baseline, a, u, **mkw)  # (B, c, *F)
            t = repeat_tree(target, c)
            vals = f(xi.reshape((B * c,) + feat), t).astype(jnp.float32)
            return jnp.sum(vals * w.astype(jnp.float32).reshape(-1))

        inc = jax.grad(chunk_sum)(jnp.zeros_like(x, dtype=jnp.float32))
        if mask is not None:  # match the unfused accumulators' masked grads
            inc = inc * expand_mask(mask, inc.ndim)
        return acc + inc, None

    def step_fused(acc, xs):
        # quadratic accumulators (idgi): per-step gradients are irreducible
        # (⟨g,g⟩, Σ c_k g_k²), but the interpolation still composes into the
        # differentiated program — grads arrive as the cotangent of a
        # per-step additive carry, never of a materialized interpolant input.
        a, w = xs

        def chunk_vals(z):
            xi = interp_add_fn(x, baseline, a, z, **mkw)
            t = repeat_tree(target, c)
            return f(xi.reshape((B * c,) + feat), t).sum()

        g = jax.grad(chunk_vals)(jnp.zeros((B, c) + feat, jnp.float32))
        return accum_fn(acc, g, w, diff=diff, **mkw), None

    if fused:
        step = step_fused_linear if spec.grad_linear else step_fused

    if state is None:
        acc0 = jnp.zeros_like(x, dtype=jnp.float32)
    else:
        acc0 = state.acc.astype(jnp.float32)
        if state_scale != 1.0:
            acc0 = acc0 * jnp.float32(state_scale)
    with jax.named_scope("repro.stage2"):  # the name device traces show
        acc, _ = jax.lax.scan(step, acc0, (a_ch, w_ch))
    attr = spec.finalize(acc, xp, baseline, mask)

    if state is not None:
        f_x, f_b = state.f_x, state.f_baseline
    elif f_x is not None:
        f_x = f_x.astype(jnp.float32)
        f_b = f(baseline, target)
    else:
        both = jnp.concatenate([xp, baseline], axis=0)
        fv = f(both, jax.tree.map(lambda t: jnp.concatenate([t, t], axis=0), target))
        f_x, f_b = fv[:B], fv[B:]
    # attr is exactly zero at masked positions, so the full sum IS the
    # real-token sum — δ measures completeness over real tokens only.
    delta = jnp.abs(attr.reshape(B, -1).sum(-1) - (f_x - f_b))
    res = IGResult(attr, f_x, f_b, delta)
    if return_state:
        return res, IGState(acc, f_x, f_b)
    return res
