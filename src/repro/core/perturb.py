"""Forward-only perturbation attribution — occlusion, RISE, LIME.

The paper's pipeline needs gradients; a serving system at scale must also
explain models it cannot differentiate (quantized, remote, black-box
endpoints — the first ROADMAP open item). This module is the second
executable class next to ``riemann``/``idgi``: instead of interpolating and
back-propagating, it evaluates the model FORWARD on a batch of masked
variants of the input and turns the f-values into per-position scores.

Mask contract (the whole class hangs off it):

  * A perturbation mask ``z`` is a (P, S) binary keep-mask over the S
    position axis: ``z=1`` keeps the input, ``z=0`` replaces the position
    with the baseline — ``x_p = z_p ⊙ x + (1 − z_p) ⊙ x′`` in embedding
    space, the same space the IG path interpolates in, so LM tokens and ViT
    patches ride unchanged.
  * Masks are drawn from keys PURE in the request index (``request_key`` —
    the same fold-in discipline as the path-ensemble expansion, DESIGN.md
    §8): replayed traffic draws bit-identical masks, batch-pad rows
    duplicate a real row's masks, and the serving engine's zero-recompile /
    padding-invariance gates extend to this class unchanged.
  * Pad positions are pinned to the baseline BEFORE perturbation
    (``mask_to_baseline``) and the final scores are multiplied by the
    real-position mask — padded positions get exactly zero attribution,
    like the gradient class.

Methods (registered in ``repro.core.methods`` with ``forward_only=True``):

  occlusion — deterministic sliding windows: score_s = the mean drop
              f(x) − f(x_p) over the windows that occlude position s.
  rise      — random binary keep-masks (Petsiuk et al., 2018):
              score_s = E[f(x_p) | z_s = 1] − E[f(x_p)], estimated from P
              Bernoulli(p_keep) masks.
  lime      — binary masks over contiguous position GROUPS (the tabular/
              sequence analogue of superpixels), exponential-kernel
              weighted ridge regression of f(x_p) on the group indicators;
              a group's coefficient is spread to its positions. The
              weighted least-squares solve is the ``kernels/lstsq`` Pallas
              kernel's job on the serving path (``solve_fn`` injection);
              the default is the pure-jnp oracle.

Everything accumulates CHUNKED sufficient statistics under ``lax.scan``
(the forward analogue of stage 2's gradient chunks): occlusion/RISE carry
(B, S) numerators/denominators, LIME carries the (B, G+1, G+1) normal
equations — so one compiled program serves any mask budget P at bounded
memory, and the accumulator consumes ``f(perturbed)`` VALUES where the
gradient class consumes VJPs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.paths import mask_to_baseline
from repro.core.probes import ScalarFn, repeat_tree


class PerturbResult(NamedTuple):
    """Forward-only analogue of ``ig.IGResult``; attributions are per
    POSITION (B, S) — the class scores positions, not features."""

    attributions: jax.Array  # (B, S) f32 per-position scores
    f_x: jax.Array  # (B,) model output at the (pinned) input
    f_baseline: jax.Array  # (B,) model output at the baseline
    delta: jax.Array  # (B,) |Σ_s score_s − (f_x − f_b)| — diagnostic only:
    # perturbation methods satisfy no completeness axiom, so δ is reported
    # for observability and never gates convergence.


class PerturbMasks(NamedTuple):
    """One request's (or one batch's) drawn masks.

    ``z`` is the (…, P, S) position keep-mask batch. LIME additionally
    carries the (…, P, G) group indicators its regression runs on and the
    (S,) position→group map; both are ``None`` for occlusion/RISE."""

    z: jax.Array  # (..., P, S) position keep-masks
    groups: Optional[jax.Array] = None  # (..., P, G) lime group masks
    group_ids: Optional[jax.Array] = None  # (S,) int32 position -> group


# ------------------------------------------------------------- mask drawing


def request_key(seed: int, s_bucket: int, index: int | jax.Array) -> jax.Array:
    """The per-request mask key: pure in (seed, bucket S, request index) —
    the SAME discipline as the path-ensemble expansion (DESIGN.md §8), so
    replay is bit-identical and pad rows duplicate a real row's stream."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), s_bucket)
    return jax.random.fold_in(base, index)


def occlusion_masks(S: int, n_masks: int) -> jax.Array:
    """(P=n_masks, S) sliding-window occlusion masks (deterministic).

    Window width ⌈S/P⌉, stride = width (the windows tile S); when fewer
    windows than P tile S, windows repeat cyclically so P is EXACTLY
    ``n_masks`` for every S — the mask batch shape is pure in (S, P), which
    keeps the serving executable set closed. Duplicate windows only enter
    the per-position average twice (numerator and denominator alike)."""
    window = -(-S // n_masks)  # ceil
    n_win = -(-S // window)
    starts = (jnp.arange(n_masks) % n_win) * window
    pos = jnp.arange(S)
    occluded = (pos[None, :] >= starts[:, None]) & (
        pos[None, :] < starts[:, None] + window
    )
    return 1.0 - occluded.astype(jnp.float32)


def rise_masks(key: jax.Array, n_masks: int, S: int, p_keep: float = 0.5) -> jax.Array:
    """(P, S) iid Bernoulli(p_keep) keep-masks."""
    return jax.random.bernoulli(key, p_keep, (n_masks, S)).astype(jnp.float32)


def default_n_groups(S: int) -> int:
    """LIME group count for a bucket width — pure in S (shape closure)."""
    return min(S, 16)


def lime_group_ids(S: int, n_groups: int) -> jax.Array:
    """(S,) int32 position→group map: contiguous, near-equal groups — the
    sequence/patch-grid analogue of superpixels."""
    return jnp.minimum(jnp.arange(S) * n_groups // S, n_groups - 1).astype(jnp.int32)


def lime_masks(key: jax.Array, n_masks: int, n_groups: int) -> jax.Array:
    """(P, G) iid Bernoulli(0.5) group keep-masks (the LIME design rows)."""
    return jax.random.bernoulli(key, 0.5, (n_masks, n_groups)).astype(jnp.float32)


def draw_masks(
    method: str,
    keys: jax.Array,
    S: int,
    n_masks: int,
    *,
    p_keep: float = 0.5,
    n_groups: int = 0,
) -> PerturbMasks:
    """Per-request mask batches for a (B,)-keyed request batch.

    ``keys``: (B,) request keys from ``request_key`` (ignored by the
    deterministic occlusion generator, which broadcasts one mask set).
    Returns ``PerturbMasks`` with leading batch axis: z (B, P, S), and for
    lime also groups (B, P, G) + the shared group_ids (S,).
    """
    B = keys.shape[0]
    if method == "occlusion":
        z = jnp.broadcast_to(occlusion_masks(S, n_masks), (B, n_masks, S))
        return PerturbMasks(z)
    if method == "rise":
        z = jax.vmap(lambda k: rise_masks(k, n_masks, S, p_keep))(keys)
        return PerturbMasks(z)
    if method == "lime":
        G = n_groups if n_groups else default_n_groups(S)
        gids = lime_group_ids(S, G)
        zg = jax.vmap(lambda k: lime_masks(k, n_masks, G))(keys)
        return PerturbMasks(zg[..., gids], zg, gids)
    raise ValueError(f"unknown perturbation method {method!r}")


# ----------------------------------------------- forward-value accumulators
#
# The forward-only MethodSpec contract: the accumulator consumes f(perturbed)
# VALUES, not gradients —
#   update(stats, vals (B, c) f32, z (B, c, S | G), *, ctx) -> stats
#   finalize(stats, *, ctx) -> (B, S) f32 scores
# ``stats`` is a per-method pytree of f32 sufficient statistics; ``ctx`` is
# the static per-call context dict built by ``attribute_from_masks``
# (endpoints, mask, P, the lime solve hook). ``init`` builds the scan carry.


def occlusion_init(B: int, S: int, G: int) -> dict:
    return {"num": jnp.zeros((B, S), jnp.float32), "den": jnp.zeros((B, S), jnp.float32)}


def occlusion_update(stats: dict, vals: jax.Array, z: jax.Array, *, ctx: dict) -> dict:
    """Accumulate the f-drop of every window onto the positions it occludes."""
    drop = ctx["f_x"][:, None] - vals  # (B, c)
    occ = 1.0 - z  # (B, c, S) occluded indicator
    return {
        "num": stats["num"] + jnp.einsum("bc,bcs->bs", drop, occ),
        "den": stats["den"] + occ.sum(axis=1),
    }


def occlusion_finalize(stats: dict, *, ctx: dict) -> jax.Array:
    den = stats["den"]
    return jnp.where(den > 0.0, stats["num"] / jnp.where(den > 0.0, den, 1.0), 0.0)


def rise_init(B: int, S: int, G: int) -> dict:
    return {
        "sz": jnp.zeros((B, S), jnp.float32),  # Σ_p f_p · z_ps
        "nz": jnp.zeros((B, S), jnp.float32),  # Σ_p z_ps
        "sv": jnp.zeros((B,), jnp.float32),  # Σ_p f_p
    }


def rise_update(stats: dict, vals: jax.Array, z: jax.Array, *, ctx: dict) -> dict:
    return {
        "sz": stats["sz"] + jnp.einsum("bc,bcs->bs", vals, z),
        "nz": stats["nz"] + z.sum(axis=1),
        "sv": stats["sv"] + vals.sum(axis=1),
    }


def rise_finalize(stats: dict, *, ctx: dict) -> jax.Array:
    """score_s = E[f | z_s = 1] − E[f]; positions never kept score 0."""
    nz = stats["nz"]
    cond = stats["sz"] / jnp.where(nz > 0.0, nz, 1.0)
    mean = stats["sv"][:, None] / jnp.float32(ctx["n_masks"])
    return jnp.where(nz > 0.0, cond - mean, 0.0)


def lime_weights(zg: jax.Array, kernel_width: float) -> jax.Array:
    """Exponential proximity kernel π_p = exp(−(1 − cover_p)² / width²) on
    the group-coverage fraction (full-coverage masks weigh most)."""
    cover = zg.mean(axis=-1)
    return jnp.exp(-((1.0 - cover) ** 2) / jnp.float32(kernel_width) ** 2)


def lime_init(B: int, S: int, G: int) -> dict:
    return {
        "A": jnp.zeros((B, G + 1, G + 1), jnp.float32),  # XᵀWX (+ intercept)
        "b": jnp.zeros((B, G + 1), jnp.float32),  # XᵀWy
    }


def lime_update(stats: dict, vals: jax.Array, zg: jax.Array, *, ctx: dict) -> dict:
    """Accumulate the weighted normal equations of f ~ [groups, 1]."""
    B, c, G = zg.shape
    xg = jnp.concatenate([zg, jnp.ones((B, c, 1), zg.dtype)], axis=-1)
    w = lime_weights(zg, ctx["kernel_width"])  # (B, c)
    return {
        "A": stats["A"] + jnp.einsum("bci,bc,bcj->bij", xg, w, xg),
        "b": stats["b"] + jnp.einsum("bci,bc,bc->bi", xg, w, vals),
    }


def lime_finalize(stats: dict, *, ctx: dict) -> jax.Array:
    """Ridge-solve the accumulated normal equations and spread each group's
    coefficient to its positions. ``group_valid`` rows (groups with no real
    position in a padded bucket) are pinned to identity by the solver, so
    their β — and therefore every pad position's score — is exactly zero."""
    gv = ctx["group_valid"]
    if gv is not None:  # intercept column is always live
        gv = jnp.concatenate([gv, jnp.ones((gv.shape[0], 1), gv.dtype)], axis=-1)
    beta = ctx["solve_fn"](stats["A"], stats["b"], mask=gv, ridge=ctx["ridge"])
    return jnp.take(beta[:, :-1], ctx["group_ids"], axis=1)  # (B, S)


_FWD = {
    "occlusion": (occlusion_init, occlusion_update, occlusion_finalize),
    "rise": (rise_init, rise_update, rise_finalize),
    "lime": (lime_init, lime_update, lime_finalize),
}


def _default_solve(A, rhs, *, mask=None, ridge=0.0):
    from repro.kernels.lstsq.ref import wls_solve_ref

    return wls_solve_ref(A, rhs, mask=mask, ridge=ridge)


# ---------------------------------------------------------------- attribute


@jax.named_scope("repro.perturb")  # the name device traces show
def attribute_from_masks(
    f: ScalarFn,
    x: jax.Array,
    baseline: jax.Array,
    target: Any,
    pm: PerturbMasks,
    *,
    method: Union[str, Any] = "occlusion",
    mask: Optional[jax.Array] = None,
    group_valid: Optional[jax.Array] = None,
    chunk: int = 0,
    ridge: float = 1e-2,
    kernel_width: float = 0.25,
    solve_fn: Optional[Callable] = None,
    f_x: Optional[jax.Array] = None,
) -> PerturbResult:
    """Forward-only attribution over pre-drawn masks — the compiled unit.

    f: (xs (N, S, *E), targets) -> (N,);  x/baseline: (B, S, *E).
    pm: batched ``PerturbMasks`` (z (B, P, S); lime adds groups/group_ids).
    mask: optional (B, S) real-position mask — pad positions are pinned to
    the baseline before perturbation and scored exactly zero.
    group_valid: optional (B, G) — lime groups containing at least one real
    position; invalid groups are pinned out of the solve (β = 0 exactly).
    chunk: masks per scan step (0 = all P at once); must divide P.
    solve_fn: the lime WLS hook ``(A, rhs, *, mask, ridge) -> beta`` —
    ``kernels.lstsq.ops.wls_solve`` on the kernel-injected serving path,
    the ``kernels.lstsq.ref`` oracle by default.
    f_x: optional known (B,) endpoint f(x) (probe reuse): only f(baseline)
    is then computed alongside the mask batch.

    Like the gradient class, masks expand OUTSIDE this function (plan time
    / batch construction) so the compiled program's shapes are pure in
    (B, S, P) and replayed traffic hits warmed executables.
    """
    from repro.core import methods as methods_mod

    spec = methods_mod.get(method)
    if not spec.forward_only:
        raise ValueError(
            f"method {spec.name!r} is gradient-based; use repro.core.ig.attribute"
        )
    init, update, finalize = _FWD[spec.accum]

    B, S = x.shape[:2]
    feat = x.shape[2:]
    P = pm.z.shape[1]
    G = pm.groups.shape[-1] if pm.groups is not None else 0
    xp = mask_to_baseline(x, baseline, mask)

    if f_x is not None:
        f_x = f_x.astype(jnp.float32)
        f_b = f(baseline, target).astype(jnp.float32)
    else:
        both = jnp.concatenate([xp, baseline], axis=0)
        fv = f(both, jax.tree.map(lambda t: jnp.concatenate([t, t], axis=0), target))
        f_x, f_b = fv[:B].astype(jnp.float32), fv[B:].astype(jnp.float32)

    ctx = {
        "f_x": f_x,
        "n_masks": P,
        "kernel_width": kernel_width,
        "ridge": ridge,
        "group_ids": pm.group_ids,
        "group_valid": group_valid,
        "solve_fn": solve_fn if solve_fn is not None else _default_solve,
    }

    c = chunk if chunk and chunk < P else P
    assert P % c == 0, f"chunk {c} must divide n_masks {P}"
    n_chunks = P // c
    z_ch = pm.z.reshape(B, n_chunks, c, S).swapaxes(0, 1)  # (n_chunks, B, c, S)
    # the accumulator's design rows: group indicators for lime, the position
    # masks themselves otherwise
    acc_rows = pm.groups if pm.groups is not None else pm.z
    r_ch = acc_rows.reshape(B, n_chunks, c, acc_rows.shape[-1]).swapaxes(0, 1)

    def step(stats, xs):
        z, rows = xs  # (B, c, S), (B, c, S|G)
        ze = z.reshape(z.shape + (1,) * len(feat))
        xi = ze * xp[:, None] + (1.0 - ze) * baseline[:, None]  # (B, c, S, *E)
        vals = f(xi.reshape((B * c, S) + feat), repeat_tree(target, c))
        vals = vals.reshape(B, c).astype(jnp.float32)
        return update(stats, vals, rows, ctx=ctx), None

    stats, _ = jax.lax.scan(step, init(B, S, G), (z_ch, r_ch))
    scores = finalize(stats, ctx=ctx)  # (B, S)
    if mask is not None:
        scores = scores * mask.astype(jnp.float32)
    delta = jnp.abs(scores.sum(-1) - (f_x - f_b))
    return PerturbResult(scores, f_x, f_b, delta)


# ------------------------------------------------------------- convenience


@dataclass(frozen=True)
class PerturbExplainer:
    """Self-contained forward-only explainer over (B, S, *E) inputs.

    Draws each row's masks from ``request_key(seed, S, row_index)`` — the
    same keying the serving engine uses with request indices, so a direct
    call and a served bucket of the same rows draw identical masks. Used by
    the golden fixtures, the quality benchmark, and the core tests; the
    serving path goes through ``ExplainEngine`` (plan-time mask expansion,
    compiled-executable cache).
    """

    f: ScalarFn
    method: str = "occlusion"
    n_masks: int = 64
    seed: int = 0
    chunk: int = 0
    p_keep: float = 0.5
    n_groups: int = 0  # 0 = default_n_groups(S)
    ridge: float = 1e-2
    kernel_width: float = 0.25
    solve_fn: Optional[Callable] = None

    def masks_for(self, B: int, S: int) -> PerturbMasks:
        keys = jax.vmap(lambda i: request_key(self.seed, S, i))(
            jnp.arange(B, dtype=jnp.uint32)
        )
        return draw_masks(
            self.method, keys, S, self.n_masks,
            p_keep=self.p_keep, n_groups=self.n_groups,
        )

    def attribute(
        self,
        x: jax.Array,
        baseline: jax.Array,
        target: Any,
        *,
        mask: Optional[jax.Array] = None,
    ) -> PerturbResult:
        B, S = x.shape[:2]
        pm = self.masks_for(B, S)
        group_valid = None
        if pm.group_ids is not None and mask is not None:
            group_valid = group_real_mask(mask, pm.group_ids, pm.groups.shape[-1])
        return attribute_from_masks(
            self.f, x, baseline, target, pm,
            method=self.method, mask=mask, group_valid=group_valid,
            chunk=self.chunk, ridge=self.ridge,
            kernel_width=self.kernel_width, solve_fn=self.solve_fn,
        )


def group_real_mask(mask: jax.Array, group_ids: jax.Array, n_groups: int) -> jax.Array:
    """(B, S) real-position mask → (B, G) "group has a real position"."""
    onehot = jax.nn.one_hot(group_ids, n_groups, dtype=jnp.float32)  # (S, G)
    return (mask.astype(jnp.float32) @ onehot > 0.0).astype(jnp.float32)


# ----------------------------------------------------- image <-> cell views
#
# Perturbation scores POSITIONS; a dense image has none, so the quality
# bake-off carves (B, H, W, C) images into a grid of cell² patches — the
# same move ViT's patchify makes — and perturbs cells. The helpers below
# are the (exact, invertible) reshape pair plus the score broadcast that
# makes insertion/deletion AUC comparable with per-pixel gradient methods.


def image_to_cells(images: jax.Array, cell: int) -> jax.Array:
    """(B, H, W, C) -> (B, (H/cell)·(W/cell), cell·cell·C) position view."""
    B, H, W, C = images.shape
    gh, gw = H // cell, W // cell
    assert gh * cell == H and gw * cell == W, (H, W, cell)
    x = images.reshape(B, gh, cell, gw, cell, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, gh * gw, cell * cell * C)


def cells_to_image(cells: jax.Array, image_shape: tuple, cell: int) -> jax.Array:
    """Inverse of ``image_to_cells``."""
    B = cells.shape[0]
    H, W, C = image_shape
    gh, gw = H // cell, W // cell
    x = cells.reshape(B, gh, gw, cell, cell, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, H, W, C)


def cell_fn(f: ScalarFn, image_shape: tuple, cell: int) -> ScalarFn:
    """Lift a pixel-space scalar fn to the (B, S, D) cell view."""

    def g(xc, target):
        return f(cells_to_image(xc, image_shape, cell), target)

    return g


def cell_scores_to_pixels(
    scores: jax.Array, image_shape: tuple, cell: int
) -> jax.Array:
    """Broadcast (B, S) cell scores to (B, H, W, C) pixel attributions
    (every pixel of a cell shares its cell's score — the ranking the
    insertion/deletion curves consume)."""
    B, S = scores.shape
    H, W, C = image_shape
    cells = jnp.broadcast_to(scores[..., None], (B, S, cell * cell * C))
    return cells_to_image(cells, image_shape, cell)
