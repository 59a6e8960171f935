"""mamba2-780m: weights, inputs, plain reference and FLOP count.

The plain reference is the Mamba-2 language model as ``mamba2-780m.json``
states it, in ``jax.numpy`` with nothing taken from the program. Each of
the 48 layers is ``x + out(gated_rmsnorm(ssm(...)))`` over an RMSNorm of
``x``; the state-space part is written as its quadratic dual form,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t,

one masked (t, s) matrix per head, which is the recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t`` summed out
(the program computes it chunk by chunk). The explained output is the
log-probability of the target token after the last real prompt position,
in embedding space, with every position of the baseline at the pad
token's embedding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.numerics import act_dtype, act_mm, mm, rmsnorm

# the configuration states bfloat16: its control is the reference with
# float8 (e4m3) weight matmuls
CONTROL = "fp8"
KIND = "tokens"
PAD_ID = 0


def d_inner(c: dict) -> int:
    return c["ssm_expand"] * c["d_model"]


def heads(c: dict) -> int:
    return d_inner(c) // c["ssm_head_dim"]


def ref_len(c: dict) -> int:
    """The reference runs every prompt right-padded to this length; the
    model is causal, so padding after the last real token changes nothing."""
    return max(c["engine"]["seq_buckets"])


def small(c: dict, *, control: bool = False) -> dict:
    """The CPU stand-in of these sizes, with the same keys: the program's
    ``reduced(mamba2-780m)`` widths, two layers, a 512-token vocabulary,
    sequences up to 32 (two SSD chunks of 16), an engine of chunk 4 and
    batches 1-4. The control test runs at the same size."""
    del control
    return dict(c, num_layers=2, d_model=64, vocab_size=512, ssm_state=16, ssm_head_dim=16,
                ssm_chunk=16,
                engine=dict(c["engine"], chunk=4, batch_buckets=[1, 2, 4], max_batch=4,
                            seq_buckets=[32]))


def layer_kinds(c: dict) -> list[str]:
    """The kind of each layer, in order: every layer is a Mamba-2 mixer."""
    return ["mamba2"] * c["num_layers"]


def program_config(c: dict):
    """The program's own config object for these sizes."""
    import dataclasses

    from repro.configs import get_config

    keys = ("num_layers", "d_model", "vocab_size", "ssm_state", "ssm_expand",
            "ssm_head_dim", "ssm_groups", "ssm_conv", "ssm_chunk", "norm_eps",
            "tie_embeddings", "param_dtype", "compute_dtype")
    return dataclasses.replace(get_config(c["name"]), **{k: c[k] for k in keys})


def init_params(c: dict, key: jax.Array):
    """The weights in the program's layout, made on the device in one call."""
    d, L, V = c["d_model"], c["num_layers"], c["vocab_size"]
    di, N, G, H, W = d_inner(c), c["ssm_state"], c["ssm_groups"], heads(c), c["ssm_conv"]

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        dt = jnp.exp(jax.random.uniform(next(ks), (L, H), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        mixer = {
            "in_z": normal((L, d, di), d ** -0.5),
            "in_x": normal((L, d, di), d ** -0.5),
            "in_B": normal((L, d, G * N), d ** -0.5),
            "in_C": normal((L, d, G * N), d ** -0.5),
            "in_dt": normal((L, d, H), d ** -0.5),
            "conv_x": normal((L, W, di), 0.5),
            "conv_B": normal((L, W, G * N), 0.5),
            "conv_C": normal((L, W, G * N), 0.5),
            "A_log": jnp.log(jax.random.uniform(next(ks), (L, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((L, H), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) == dt
            "norm": jnp.ones((L, di), jnp.float32),
            "out": normal((L, di, d), di ** -0.5 / math.sqrt(L)),
        }
        return {
            "embed": {"embedding": normal((V, d), 0.02)},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "layers": ({"norm1": {"scale": jnp.ones((L, d), jnp.float32)}, "mixer": mixer},),
            "rem": (),
        }

    return jax.jit(make)(key)


def _conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Causal depthwise convolution: out_t = sum_i w[W-1-i] x_{t-i}."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    w = w.astype(x.dtype)
    out = xp[:, 0:S] * w[0]
    for i in range(1, W):
        out = out + xp[:, i:i + S] * w[i]
    return out


def _mixer(c: dict, p: dict, h: jax.Array, mode: str) -> jax.Array:
    dt_, eps = act_dtype(mode), c["norm_eps"]
    n, S, _ = h.shape
    H, P = heads(c), c["ssm_head_dim"]
    z = mm(mode, "nsd,de->nse", h, p["in_z"]).astype(dt_)
    x = mm(mode, "nsd,de->nse", h, p["in_x"]).astype(dt_)
    Bm = mm(mode, "nsd,de->nse", h, p["in_B"]).astype(dt_)
    Cm = mm(mode, "nsd,de->nse", h, p["in_C"]).astype(dt_)
    dt = mm(mode, "nsd,dh->nsh", h, p["in_dt"]).astype(jnp.float32)
    x = jax.nn.silu(_conv(x, p["conv_x"]))
    Bm = jax.nn.silu(_conv(Bm, p["conv_B"]))
    Cm = jax.nn.silu(_conv(Cm, p["conv_C"]))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # (n, S, H)
    cum = jnp.cumsum(dt * A, axis=1)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
    G = c["ssm_groups"]
    Bg = Bm.reshape(n, S, G, -1)
    Cg = Cm.reshape(n, S, G, -1)
    cb = act_mm(mode, "ntgk,nsgk->ntsg", Cg, Bg).astype(jnp.float32)  # (n, t, s, G)
    cb = jnp.repeat(cb, H // G, axis=-1)  # heads share their group's B and C
    w = cb * decay * dt[:, None, :, :]  # (n, t, s, H)
    xh = x.reshape(n, S, H, P)
    y = act_mm(mode, "ntsh,nshp->nthp", w, xh).astype(jnp.float32)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(n, S, H * P).astype(dt_)
    g = rmsnorm(y * jax.nn.silu(z), p["norm"], eps)
    return mm(mode, "nse,ed->nsd", g, p["out"]).astype(dt_)


def logprob(c: dict, params, e: jax.Array, aux: dict, mode: str = "f32") -> jax.Array:
    """(N, S, d) embeddings -> (N,) log-probability of ``aux["target"]``
    after position ``aux["pos"]``."""
    dt_, eps = act_dtype(mode), c["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        return x + _mixer(c, lp["mixer"], rmsnorm(x, lp["norm1"]["scale"], eps), mode), None

    x, _ = jax.lax.scan(layer, e.astype(dt_), params["layers"][0])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    h = x[jnp.arange(x.shape[0]), aux["pos"]]
    lg = mm(mode, "nd,vd->nv", h, params["embed"]["embedding"]).astype(jnp.float32)
    lp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(lp, aux["target"][:, None], axis=-1)[:, 0]


def make_inputs(c: dict, params, traffic: dict, key: jax.Array, rng, n: int) -> list[dict]:
    """``n`` prompts of uniform random tokens with a random target token.
    The lengths spread evenly over [min_len, max_len] and the seed orders
    them, so every seed serves the same set of lengths."""
    del params, key
    lo, hi, V = traffic["min_len"], traffic["max_len"], c["vocab_size"]
    lens = lo + (np.arange(n) * (hi - lo + 1)) // n
    rng.shuffle(lens)
    return [{"tokens": rng.integers(1, V, size=int(s)).astype(np.int32),
             "target": int(rng.integers(0, V)), "features": None} for s in lens]


def ref_inputs(c: dict, params, inp: dict, mode: str = "f32"):
    """(x, baseline, aux) of one request for the plain reference: (S, d)
    embeddings at ``ref_len`` and the per-row arguments of ``logprob``."""
    toks = np.full((ref_len(c),), PAD_ID, np.int32)
    toks[: len(inp["tokens"])] = inp["tokens"]
    table = params["embed"]["embedding"]
    dt_ = act_dtype(mode)
    x = table[jnp.asarray(toks)].astype(dt_)
    b = jnp.broadcast_to(table[PAD_ID].astype(dt_), x.shape)
    return x, b, {"target": np.int32(inp["target"]), "pos": np.int32(len(inp["tokens"]) - 1)}


# ------------------------------------------------------------------ FLOPs
# The work the algorithm needs at a request's real length S: matmul FLOPs
# (2 per multiply-add), the state-space part over the causal (t, s) pairs
# of each chunk plus the state passed between chunks, and the logits at
# the one position explained. A VJP is taken with respect to the input
# only: a weight matmul costs its forward again; the state-space products
# twice, since all their operands depend on the input.


def _per_layer(c: dict, S: int) -> tuple[float, float]:
    d, di, N, G = c["d_model"], d_inner(c), c["ssm_state"], c["ssm_groups"]
    H, P, W, cl = heads(c), c["ssm_head_dim"], c["ssm_conv"], c["ssm_chunk"]
    weights = 2 * S * d * (2 * di + 2 * G * N + H) + 2 * S * di * d
    conv = 2 * S * W * (di + 2 * G * N)
    pairs, n_chunks, rest = 0, 0, S
    while rest > 0:
        k = min(cl, rest)
        pairs += k * (k + 1) // 2
        n_chunks += 1
        rest -= k
    ssd = pairs * (2 * N * G + 2 * H * P)
    if n_chunks > 1:  # state out of each chunk and into each later one
        ssd += 2 * (2 * S * H * P * N)
    return float(weights), float(conv + ssd)


def flops_forward(c: dict, S: int) -> float:
    weights, ssd = _per_layer(c, S)
    return c["num_layers"] * (weights + ssd) + 2.0 * c["d_model"] * c["vocab_size"]


def flops_vjp(c: dict, S: int) -> float:
    weights, ssd = _per_layer(c, S)
    return c["num_layers"] * (weights + 2 * ssd) + 2.0 * c["d_model"] * c["vocab_size"]


def flops_embed(c: dict, S: int) -> float:
    return 0.0  # a table lookup


def param_bytes(c: dict) -> float:
    d, di, N, G = c["d_model"], d_inner(c), c["ssm_state"], c["ssm_groups"]
    H, W, L = heads(c), c["ssm_conv"], c["num_layers"]
    per_layer = d + d * (2 * di + 2 * G * N + H) + W * (di + 2 * G * N) + 3 * H + di + di * d
    return 4.0 * (c["vocab_size"] * d + d + L * per_layer)
