"""ViT encoder — patch-level attributions on the attention hot path.

Pre-norm transformer over patch embeddings (linear patch projection + learned
position embedding, no CLS token — masked mean-pool head), built from the
same blocks as the LM (rmsnorm / GQA qkv / SwiGLU mlp) so
``dispatch_attention`` — and therefore the flash custom-VJP kernel — is
shared between model families.

IG path note: the patch projection is affine, so a straight line in pixel
space maps to a straight line in embedding space — attributing in embedding
space (what ``ExplainEngine`` buckets) is exactly the paper's pixel-space IG
with per-patch aggregation built in.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.vit import VitConfig
from repro.models import attention as attn
from repro.models import common
from repro.models.common import ParamDef, scan_or_unroll, stack_defs
from repro.models.layers import mlp, mlp_def, rmsnorm, rmsnorm_def

# ---------------------------------------------------------------- parameters


def _layer_def(cfg: VitConfig) -> dict:
    return {
        "norm1": rmsnorm_def(cfg.d_model),
        "mixer": attn.attn_def(cfg),  # duck-typed VitConfig (see configs/vit.py)
        "norm2": rmsnorm_def(cfg.d_model),
        "ffn": mlp_def(cfg.d_model, cfg.d_ff),
    }


def param_defs(cfg: VitConfig) -> dict:
    d = cfg.d_model
    return {
        "patch_proj": ParamDef((cfg.patch_dim, d), ("frontend", "embed")),
        "patch_bias": ParamDef((d,), (None,), init="zeros"),
        "pos_embed": ParamDef((cfg.num_patches, d), (None, "embed"), scale=0.02),
        "layers": stack_defs(_layer_def(cfg), cfg.num_layers),
        "final_norm": rmsnorm_def(d),
        "head": {
            "w": ParamDef((d, cfg.num_classes), ("embed", None)),
            "b": ParamDef((cfg.num_classes,), (None,), init="zeros"),
        },
    }


def init(cfg: VitConfig, key: jax.Array) -> Any:
    return common.init_params(key, param_defs(cfg))


# ---------------------------------------------------------------- embedding


def patchify(cfg: VitConfig, images: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, num_patches, patch_dim) row-major patch features."""
    B, H, W, C = images.shape
    p = cfg.patch_size
    x = images.reshape(B, H // p, p, W // p, p, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, (H // p) * (W // p), p * p * C)


def embed_features(cfg: VitConfig, params: Any, feats: jax.Array) -> jax.Array:
    """Patch features -> backbone embeddings (the IG interpolation space)."""
    dt = jnp.dtype(cfg.compute_dtype)
    e = feats.astype(dt) @ params["patch_proj"].astype(dt) + params["patch_bias"].astype(dt)
    S, pe = e.shape[1], params["pos_embed"].astype(dt)
    if S <= pe.shape[0]:
        pe = pe[:S]
    else:  # bucket padded past the patch grid: padded slots carry no posemb
        pe = jnp.pad(pe, ((0, S - pe.shape[0]), (0, 0)))
    return e + pe[None]


# ------------------------------------------------------------------ backbone


def encode(
    cfg: VitConfig,
    params: Any,
    e: jax.Array,  # (B, S, d)
    *,
    lengths: Optional[jax.Array] = None,  # (B,) valid patch counts
    remat: bool = False,  # recompute each layer in the backward pass
) -> jax.Array:
    dt = e.dtype

    def body(x, lp):
        h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
        q, k, v = attn.qkv(lp["mixer"], h, dt)
        o = attn.dispatch_attention(
            cfg, q, k, v, mixer="attn", causal=False, kv_len=lengths
        )
        x = x + attn.out_proj(lp["mixer"], o, dt)
        h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
        return x + mlp(lp["ffn"], h), None

    x, _ = scan_or_unroll(jax.checkpoint(body) if remat else body, e, params["layers"])
    return rmsnorm(params["final_norm"], x, cfg.norm_eps)


def pool_logits(
    cfg: VitConfig,
    params: Any,
    h: jax.Array,  # (B, S, d)
    *,
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Masked mean-pool over valid patches -> (B, num_classes) logits."""
    if lengths is None:
        pooled = h.mean(axis=1)
    else:
        m = (jnp.arange(h.shape[1])[None, :] < lengths[:, None]).astype(h.dtype)
        pooled = (h * m[..., None]).sum(1) / jnp.maximum(m.sum(1, keepdims=True), 1.0)
    dt = h.dtype
    return pooled @ params["head"]["w"].astype(dt) + params["head"]["b"].astype(dt)


def forward(cfg: VitConfig, params: Any, images: jax.Array) -> jax.Array:
    """images: (B, H, W, C) -> logits (B, num_classes)."""
    e = embed_features(cfg, params, patchify(cfg, images))
    return pool_logits(cfg, params, encode(cfg, params, e))


def prob_fn(cfg: VitConfig, params: Any, images: jax.Array, target: jax.Array) -> jax.Array:
    """Target-class probability — the paper's IG output function f."""
    p = jax.nn.softmax(forward(cfg, params, images), axis=-1)
    return jnp.take_along_axis(p, target[:, None], axis=-1)[:, 0]


# ------------------------------------------------------------------- facade


class VitModel:
    """ExplainEngine-facing facade (the feature-request counterpart of
    ``registry.Model``): requests carry patchified images in ``features``."""

    def __init__(self, cfg: VitConfig):
        self.cfg = cfg

    def param_defs(self):
        return param_defs(self.cfg)

    def init(self, key: jax.Array):
        return init(self.cfg, key)

    def embed_inputs(self, params, batch):
        raise TypeError(
            "VitModel has no token embedding: ExplainRequests for a ViT must "
            "carry features=patchify(cfg, image) (see models/vit.patchify)"
        )

    def embed_features(self, params, feats: jax.Array) -> jax.Array:
        return embed_features(self.cfg, params, feats)

    def target_logprob_at_fn(self, params, *, remat: bool = False):
        """f(embeds, aux) -> (B,) target-class log-prob; aux["pos"] is the
        last valid patch index, so lengths = pos + 1 masks bucket padding."""

        def f(e: jax.Array, aux: dict) -> jax.Array:
            lengths = aux["pos"] + 1
            h = encode(self.cfg, params, e, lengths=lengths, remat=remat)
            lg = pool_logits(self.cfg, params, h, lengths=lengths).astype(jnp.float32)
            rows = jnp.arange(e.shape[0])
            return jax.nn.log_softmax(lg, axis=-1)[rows, aux["target"]]

        return f
