"""The plain explanation of one request, and the comparison that decides
``correct``.

``Plain`` evaluates a configuration's plain reference (``bench/configs``)
at one precision: f along the straight path from the baseline to the
input, its gradient there (as a per-position contribution
``sum_d (x - x')_d df/de_d``), and f under occlusion masks. Values are
cached by path position, so rungs that share nodes share the work.

``explain`` is the whole algorithm written plainly on top of it (probe,
paper schedule, ladder, Riemann sum; or occlusion windows). Run in the
reference precision it is the reference; run one precision lower it is the
control, which ``correct`` must refuse.

``compare`` holds one served answer to the reference at the rung the
answer says it exited at.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import schedule as sch

NODE_BATCH = 8


@functools.lru_cache(maxsize=None)
def _fns(model: Any, sizes_key: str, mode: str):
    """Jitted value / gradient / masked-value functions of one configuration
    at one precision; the weights are an argument, never a constant."""
    import json

    c = json.loads(sizes_key)

    def batch_aux(aux, n):
        return {k: jnp.full((n,), v) for k, v in aux.items()}

    def path(x, xb, alphas):
        a = alphas.astype(x.dtype).reshape((-1,) + (1,) * x.ndim)
        return xb[None] + a * (x - xb)[None]

    def values(params, x, xb, aux, alphas):
        return model.logprob(c, params, path(x, xb, alphas), batch_aux(aux, alphas.shape[0]), mode)

    def grads(params, x, xb, aux, alphas):
        aux_n = batch_aux(aux, alphas.shape[0])
        g = jax.grad(lambda e: model.logprob(c, params, e, aux_n, mode).sum())(
            path(x, xb, alphas))
        diff = (x - xb).astype(jnp.float32)
        return jnp.einsum("nsd,sd->ns", g.astype(jnp.float32), diff,
                          precision=jax.lax.Precision.HIGHEST)

    def masked(params, x, xb, aux, z):
        e = z[..., None].astype(x.dtype) * x[None] + (1 - z[..., None]).astype(x.dtype) * xb[None]
        return model.logprob(c, params, e, batch_aux(aux, z.shape[0]), mode)

    return jax.jit(values), jax.jit(grads), jax.jit(masked)


def served_mode(sizes: dict) -> str:
    """The precision mode a configuration is served in."""
    return {"float32": "f32", "bfloat16": "bf16"}[sizes["compute_dtype"]]


class Plain:
    """One request under a configuration's plain reference at one precision."""

    def __init__(self, model, sizes: dict, params, inp: dict, mode: str):
        import json

        self.mode = mode
        self.params = params
        self.n_real = len(inp["tokens"])
        self.x, self.xb, self.aux = model.ref_inputs(sizes, params, inp, mode)
        self._values, self._grads, self._masked = _fns(
            model, json.dumps(sizes, sort_keys=True), mode)
        self._v: dict[float, float] = {}
        self._g: dict[float, np.ndarray] = {}

    def _run(self, fn, keys: list, cache: dict) -> None:
        todo = [k for k in dict.fromkeys(keys) if k not in cache]
        for lo in range(0, len(todo), NODE_BATCH):
            part = todo[lo:lo + NODE_BATCH]
            pad = part + [part[-1]] * (NODE_BATCH - len(part))
            with jax.default_matmul_precision("highest"):
                out = np.asarray(fn(self.params, self.x, self.xb, self.aux,
                                    jnp.asarray(pad, jnp.float32)), np.float64)
            for k, v in zip(part, out):
                cache[k] = v

    def values(self, alphas) -> np.ndarray:
        keys = [float(a) for a in alphas]
        self._run(self._values, keys, self._v)
        return np.asarray([self._v[k] for k in keys])

    def contribs(self, alphas) -> np.ndarray:
        """(len(alphas), real positions) gradient contributions."""
        keys = [float(a) for a in alphas]
        self._run(self._grads, keys, self._g)
        return np.stack([self._g[k][: self.n_real] for k in keys])

    def masked_values(self, z: np.ndarray) -> np.ndarray:
        out = []
        for lo in range(0, len(z), NODE_BATCH):
            part = z[lo:lo + NODE_BATCH]
            pad = np.concatenate([part, np.repeat(part[-1:], NODE_BATCH - len(part), 0)])
            with jax.default_matmul_precision("highest"):
                out.append(np.asarray(self._masked(self.params, self.x, self.xb, self.aux,
                                                   jnp.asarray(pad, jnp.float32)))[: len(part)])
        return np.concatenate(out).astype(np.float64)


# ----------------------------------------------------------- occlusion


def occlusion_masks(S: int, P: int) -> np.ndarray:
    """P keep-masks over S positions: windows of width ceil(S/P) tiling S,
    repeated cyclically until there are P of them."""
    width = -(-S // P)
    n_win = -(-S // width)
    z = np.ones((P, S), np.float32)
    for p in range(P):
        start = (p % n_win) * width
        z[p, start:start + width] = 0.0
    return z


def occlusion(row: Plain, S: int, P: int) -> dict:
    fx, fb = row.values([1.0, 0.0])
    z = occlusion_masks(S, P)[:, : row.n_real]  # windows over padding change nothing
    keep = np.ones((P, row.x.shape[0]), np.float32)
    keep[:, : row.n_real] = z
    drop = fx - row.masked_values(keep)
    occ = 1.0 - z.astype(np.float64)
    den = occ.sum(0)
    scores = np.where(den > 0, (drop[:, None] * occ).sum(0) / np.maximum(den, 1), 0.0)
    return {"token_scores": scores, "f_x": fx, "f_baseline": fb,
            "delta": abs(scores.sum() - (fx - fb))}


# ------------------------------------------------------------- gradient


def probe(row: Plain, traffic: dict) -> tuple[np.ndarray, np.ndarray]:
    """(probe values, allocation of the base rung's steps to intervals).
    ``paper`` probes f at the ``n_int + 1`` interval boundaries and spreads
    the steps by their |f| changes; ``uniform`` needs only the endpoints
    and puts every step in one interval (midpoint nodes)."""
    m0 = traffic["m"]
    if traffic["schedule"] == "uniform":
        return row.values([0.0, 1.0]), np.array([m0])
    if traffic["schedule"] != "paper":
        raise ValueError(f"no plain schedule {traffic['schedule']!r}")
    n_int = traffic["n_int"]
    vals = row.values(np.arange(n_int + 1) / n_int)
    return vals, sch.paper_alloc(vals, m0)


def ig_at(row: Plain, alloc: np.ndarray, m0: int, m: int) -> np.ndarray:
    a, w = sch.rung(alloc, m0, m)
    return (w[:, None] * row.contribs(a)).sum(0)


def explain(row: Plain, traffic: dict, S: int) -> dict:
    """What the plain algorithm serves for one request (the control)."""
    if traffic["method"] == "occlusion":
        return occlusion(row, S, traffic["n_masks"])
    m0 = traffic["m"]
    vals, alloc = probe(row, traffic)
    fx, fb = vals[-1], vals[0]
    threshold = traffic.get("tol", 0.0) * abs(fx - fb)
    m = m0
    while True:
        scores = ig_at(row, alloc, m0, m)
        delta = abs(scores.sum() - (fx - fb))
        top = m >= traffic.get("m_max", m0)
        if top or not traffic.get("adaptive") or delta <= threshold:
            break
        m *= 2
    return {"token_scores": scores, "f_x": fx, "f_baseline": fb, "delta": delta,
            "m_used": m, "threshold": threshold}


def compare(row: Plain, traffic: dict, S: int, got: dict,
            slack: float = 0.0) -> dict[str, float]:
    """The numbers by which one served answer departs from the reference.

    attr_err      max |score - reference score| / max |reference score|
    attr_cos_err  1 - cosine(scores, reference scores): the attribution's
                  direction, which rounding noise spread over many
                  positions barely turns and a wrong answer turns far
    attr_norm_err |norm(scores) / norm(reference scores) - 1|: the
                  attribution's size, which the direction does not see
    endpoint_err  max(|f(x) error|, |f(x') error|), in nats (f is a
                  log-probability)
    delta_err     |delta - reference delta|, in nats
    exit_err      (adaptive) how far, in nats, the reference's delta lies on
                  the wrong side of the threshold for the rung the answer
                  exited at: above it where the answer stopped below the
                  top, or below it a rung lower where the answer went on
    A gradient answer is compared at the rung it exited at, on the nodes
    of the reference's probes. A paper schedule's allocation turns on the
    probes' rounding where an interval's |f| change is small, and the
    answer does not carry its probes: there every allocation that probes
    within ``slack`` nats of the reference's give (the cell's endpoint
    limit: what the answer's f may be off by anywhere on the path) is the
    reference's, and the nearest counts. From rung 64 on, refinement puts
    some children exactly on a cell's edge, where the last bit of the
    arithmetic decides the node set (``schedule.rung``); there the float64
    and float32 node sets are both the reference's, and the nearer counts.
    """
    scores = np.asarray(got["token_scores"], np.float64)
    if scores.shape != (row.n_real,) or not np.all(np.isfinite(scores)):
        return {"attr_err": float("inf"), "attr_cos_err": float("inf"),
                "attr_norm_err": float("inf")}
    if traffic["method"] == "occlusion":
        ref = occlusion(row, S, traffic["n_masks"])
        fx, fb = ref["f_x"], ref["f_baseline"]
        return {
            "attr_err": _rel(scores, ref["token_scores"]),
            "attr_cos_err": _cos_err(scores, ref["token_scores"]),
            "attr_norm_err": _norm_err(scores, ref["token_scores"]),
            "endpoint_err": max(abs(got["f_x"] - fx), abs(got["f_baseline"] - fb)),
        }
    m0 = traffic["m"]
    m = int(got.get("m_used", m0))
    vals, alloc = probe(row, traffic)
    fx, fb = vals[-1], vals[0]
    allocs = sch.candidate_allocs(vals, m0, slack) if traffic["schedule"] == "paper" else [alloc]
    nodes = [n for al in allocs for n in
             (sch.variants(al, m0, m) if m >= sch.TIE_RUNG else [sch.rung(al, m0, m)])]
    best = None
    for a, w in nodes:
        ref_scores = (w[:, None] * row.contribs(a)).sum(0)
        err = _rel(scores, ref_scores)
        if best is None or err < best[0]:
            best = (err, a, w, ref_scores)
    err, a, w, ref_scores = best
    delta = abs(ref_scores.sum() - (fx - fb))
    out = {"attr_err": err, "attr_cos_err": _cos_err(scores, ref_scores),
           "attr_norm_err": _norm_err(scores, ref_scores),
           "endpoint_err": max(abs(got["f_x"] - fx), abs(got["f_baseline"] - fb)),
           "delta_err": abs(got["delta"] - delta)}
    if traffic.get("adaptive"):
        thr = traffic["tol"] * abs(fx - fb)
        exit_err = 0.0
        if m < traffic["m_max"]:  # stopped: the reference must agree it converged
            exit_err = max(exit_err, delta - thr)
        if m > m0:  # went on: the reference must not have converged a rung lower
            h = m // 2  # nested: rung m/2 is the first half of the nodes, weights doubled
            below = abs((2 * w[:h, None] * row.contribs(a[:h])).sum() - (fx - fb))
            exit_err = max(exit_err, thr - below)
        out["exit_err"] = max(exit_err, 0.0)
    return out


def _cos_err(got: np.ndarray, ref: np.ndarray) -> float:
    den = np.linalg.norm(got) * np.linalg.norm(ref)
    return float(1.0 - np.dot(got, ref) / den) if den > 0 else 1.0


def _norm_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(abs(np.linalg.norm(got) / max(np.linalg.norm(ref), 1e-30) - 1.0))


def _rel(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
