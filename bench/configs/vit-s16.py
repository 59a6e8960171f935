"""vit-s16: weights, inputs, plain reference and FLOP count.

The plain reference is the encoder as ``vit-s16.json`` states it, written
out in ``jax.numpy`` with nothing taken from the program: patch projection
plus position embedding, 12 pre-norm blocks (RMSNorm, 6-head full
attention, SwiGLU MLP), final RMSNorm, mean pool, linear head, log-softmax
at the target class. The explanation is taken in embedding space, where
the program takes it: the input is the embedded image, the baseline the
embedded black image.
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
KIND = "image"


def num_patches(c: dict) -> int:
    return (c["image_size"] // c["patch_size"]) ** 2


def patch_dim(c: dict) -> int:
    return c["patch_size"] ** 2 * c["channels"]


def small(c: dict, *, control: bool = False) -> dict:
    """The CPU stand-in of these sizes, with the same keys: the program's
    ``reduced_vit()`` widths on 32 px images (64 patches), two layers, an
    engine of chunk 4 and batches 1-4. ``control`` gives the control test's
    size instead: published widths and depth on 64 px images (16 patches),
    the published engine."""
    if control:
        return dict(c, image_size=64, engine=dict(c["engine"], seq_buckets=[16]))
    return dict(c, image_size=32, patch_size=4, num_classes=10, num_layers=2, d_model=64,
                num_heads=4, d_ff=128,
                engine=dict(c["engine"], chunk=4, batch_buckets=[1, 2, 4], max_batch=4,
                            seq_buckets=[64]))


def layer_kinds(c: dict) -> list[str]:
    """The kind of each layer, in order: every block is alike."""
    return ["attention+mlp"] * c["num_layers"]


def program_config(c: dict):
    """The program's own config object for these sizes."""
    from repro.configs.vit import VitConfig

    keys = ("image_size", "patch_size", "channels", "num_classes", "num_layers",
            "d_model", "num_heads", "d_ff", "norm_eps", "param_dtype", "compute_dtype")
    return VitConfig(name=c["name"], **{k: c[k] for k in keys})


def init_params(c: dict, key: jax.Array):
    """The weights in the program's layout, made on the device in one call."""
    d, L, H = c["d_model"], c["num_layers"], c["num_heads"]
    hd, f, pd, S, C = d // H, c["d_ff"], patch_dim(c), num_patches(c), c["num_classes"]

    def make(key):
        ks = iter(jax.random.split(key, 12))

        def normal(shape, fan_in):
            return jax.random.normal(next(ks), shape, jnp.float32) / math.sqrt(fan_in)

        ones = lambda *s: jnp.ones(s, jnp.float32)
        return {
            "patch_proj": normal((pd, d), pd),
            "patch_bias": jnp.zeros((d,), jnp.float32),
            "pos_embed": 0.02 * jax.random.normal(next(ks), (S, d), jnp.float32),
            "layers": {
                "norm1": {"scale": ones(L, d)},
                "mixer": {
                    "wq": normal((L, d, H, hd), d),
                    "wk": normal((L, d, H, hd), d),
                    "wv": normal((L, d, H, hd), d),
                    "wo": normal((L, H, hd, d), d),
                },
                "norm2": {"scale": ones(L, d)},
                "ffn": {
                    "wi_gate": normal((L, d, f), d),
                    "wi_up": normal((L, d, f), d),
                    "wo": normal((L, f, d), f),
                },
            },
            "final_norm": {"scale": ones(d)},
            "head": {"w": normal((d, C), d), "b": jnp.zeros((C,), jnp.float32)},
        }

    return jax.jit(make)(key)


def patchify(c: dict, images: jax.Array) -> jax.Array:
    """(N, H, W, C) -> (N, patches, patch_dim), patches in row-major order."""
    n, h, w, ch = images.shape
    p = c["patch_size"]
    x = images.reshape(n, h // p, p, w // p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, (h // p) * (w // p), p * p * ch)


def embed(c: dict, params, features: jax.Array, mode: str = "f32"):
    """(N, S, patch_dim) features -> (embedded input, embedded black image)."""
    dt = act_dtype(mode)
    bias = (params["patch_bias"] + params["pos_embed"]).astype(dt)
    x = mm(mode, "nsp,pd->nsd", features, params["patch_proj"]).astype(dt) + bias
    return x, jnp.broadcast_to(bias, x.shape)


def logits(c: dict, params, e: jax.Array, mode: str = "f32") -> jax.Array:
    """(N, S, d) embeddings -> (N, classes) float32 logits."""
    dt, eps, H = act_dtype(mode), c["norm_eps"], c["num_heads"]
    hd = c["d_model"] // H

    def block(x, lp):
        h = rmsnorm(x, lp["norm1"]["scale"], eps)
        q = mm(mode, "nsd,dhk->nshk", h, lp["mixer"]["wq"]).astype(dt)
        k = mm(mode, "nsd,dhk->nshk", h, lp["mixer"]["wk"]).astype(dt)
        v = mm(mode, "nsd,dhk->nshk", h, lp["mixer"]["wv"]).astype(dt)
        s = act_mm(mode, "nqhk,nthk->nhqt", q * (hd ** -0.5), k).astype(jnp.float32)
        a = jax.nn.softmax(s, axis=-1).astype(dt)
        o = act_mm(mode, "nhqt,nthk->nqhk", a, v).astype(dt)
        x = x + mm(mode, "nshk,hkd->nsd", o, lp["mixer"]["wo"]).astype(dt)
        h = rmsnorm(x, lp["norm2"]["scale"], eps)
        g = mm(mode, "nsd,df->nsf", h, lp["ffn"]["wi_gate"]).astype(dt)
        u = mm(mode, "nsd,df->nsf", h, lp["ffn"]["wi_up"]).astype(dt)
        x = x + mm(mode, "nsf,fd->nsd", jax.nn.silu(g) * u, lp["ffn"]["wo"]).astype(dt)
        return x, None

    x, _ = jax.lax.scan(block, e.astype(dt), params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    pooled = x.mean(axis=1)
    out = mm(mode, "nd,dc->nc", pooled, params["head"]["w"]).astype(jnp.float32)
    return out + params["head"]["b"].astype(jnp.float32)


def logprob(c: dict, params, e: jax.Array, aux: dict, mode: str = "f32") -> jax.Array:
    """The explained output: log-probability of ``aux["target"]``, (N,)."""
    lp = jax.nn.log_softmax(logits(c, params, e, mode), axis=-1)
    return jnp.take_along_axis(lp, aux["target"][:, None], axis=-1)[:, 0]


def make_inputs(c: dict, params, traffic: dict, key: jax.Array, rng, n: int) -> list[dict]:
    """``n`` distinct synthetic images (uniform pixels), each explained for
    the class the reference predicts for it."""
    del traffic, rng
    S = num_patches(c)
    feats, targets = [], []
    predict = jax.jit(lambda p, f: jnp.argmax(logits(c, p, embed(c, p, f)[0]), -1))
    for lo in range(0, n, 64):
        k = jax.random.fold_in(key, lo)
        imgs = jax.random.uniform(
            k, (min(64, n - lo), c["image_size"], c["image_size"], c["channels"]))
        f = patchify(c, imgs)
        with jax.default_matmul_precision("highest"):
            targets.append(np.asarray(predict(params, f)))
        feats.append(np.asarray(f, np.float32))
    feats, targets = np.concatenate(feats), np.concatenate(targets)
    tokens = np.arange(S, dtype=np.int32)
    return [{"tokens": tokens, "target": int(t), "features": f}
            for f, t in zip(feats, targets)]


def ref_inputs(c: dict, params, inp: dict, mode: str = "f32"):
    """(x, baseline, aux) of one request for the plain reference: (S, d)
    embeddings and the per-row arguments of ``logprob``."""
    x, b = embed(c, params, jnp.asarray(inp["features"])[None], mode)
    return x[0], b[0], {"target": np.int32(inp["target"])}


# ------------------------------------------------------------------ FLOPs
# The work the algorithm needs at a request's real length: matmul FLOPs
# (2 per multiply-add). A VJP is taken with respect to the input only, so a
# weight matmul costs its forward again (the input gradient) and an
# attention product twice (both of its operands depend on the input).


def flops_forward(c: dict, S: int) -> float:
    d, f, L = c["d_model"], c["d_ff"], c["num_layers"]
    weights = 2 * S * d * (3 * d) + 2 * S * d * d + 3 * 2 * S * d * f
    attn = 2 * (2 * S * S * d)
    return float(L * (weights + attn) + 2 * d * c["num_classes"])


def flops_vjp(c: dict, S: int) -> float:
    d, f, L = c["d_model"], c["d_ff"], c["num_layers"]
    weights = 2 * S * d * (3 * d) + 2 * S * d * d + 3 * 2 * S * d * f
    attn = 2 * (2 * S * S * d)
    return float(L * (weights + 2 * attn) + 2 * d * c["num_classes"])


def flops_embed(c: dict, S: int) -> float:
    """The patch projection, once per request."""
    return float(2 * S * patch_dim(c) * c["d_model"])


def param_bytes(c: dict) -> float:
    d, f, L, C = c["d_model"], c["d_ff"], c["num_layers"], c["num_classes"]
    n = patch_dim(c) * d + d + num_patches(c) * d
    n += L * (2 * d + 4 * d * d + 3 * d * f) + d + d * C + C
    return float(4 * n)
