"""Model facade + dry-run input specs.

``Model`` binds an ArchConfig to the functional model code; ``input_specs``
returns ``jax.ShapeDtypeStruct`` stand-ins for every input of the step being
lowered (weak-type-correct, shardable, no device allocation).
"""
from __future__ import annotations

from functools import cached_property, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import lm


class Model:
    """Thin namespace binding cfg -> the functional model API."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- params ------------------------------------------------------------
    def param_defs(self):
        return lm.param_defs(self.cfg)

    def init(self, key: jax.Array):
        return lm.init(self.cfg, key)

    def abstract_params(self):
        return lm.abstract_params(self.cfg)

    # -- training ----------------------------------------------------------
    def loss(self, params, batch, *, remat: bool = False):
        return lm.loss(self.cfg, params, batch, remat=remat)

    # -- inference ---------------------------------------------------------
    def forward_hidden(self, params, batch, **kw):
        return lm.forward_hidden(self.cfg, params, batch, **kw)

    def logits(self, params, h):
        return lm.logits(self.cfg, params, h)

    def prefill(self, params, batch, max_len: int, *, kv_slots: int = 0):
        return lm.prefill(self.cfg, params, batch, max_len, kv_slots=kv_slots)

    def decode_step(self, params, cache, token):
        return lm.decode_step(self.cfg, params, cache, token)

    def init_cache(self, batch: int, max_len: int, *, kv_slots: int = 0):
        return lm.init_cache(self.cfg, batch, max_len, kv_slots=kv_slots)

    # -- IG hooks (embedding-space path) ------------------------------------
    def embed_inputs(self, params, batch):
        return lm.embed_inputs(self.cfg, params, batch)

    def hidden_from_embeds(self, params, e, **kw):
        return lm.hidden_from_embeds(self.cfg, params, e, **kw)

    def target_logprob_fn(self, params, *, target_pos: int = -1):
        """Returns f(embeds, target_token) -> (B,) log-prob — the IG output.

        The paper uses target-class probability of a classifier; the LM
        analogue is the next-token probability at ``target_pos``.
        """

        def f(e: jax.Array, target: jax.Array) -> jax.Array:
            h, _ = lm.hidden_from_embeds(self.cfg, params, e)
            lg = lm.logits(self.cfg, params, h[:, target_pos]).astype(jnp.float32)
            return jax.nn.log_softmax(lg, axis=-1)[jnp.arange(e.shape[0]), target]

        return f

    def target_logprob_at_fn(self, params, *, remat: bool = False):
        """Per-example-position variant for shape-bucketed serving.

        Returns f(embeds, aux) -> (B,) with aux = {"target": (B,) token ids,
        "pos": (B,) position of each example's last REAL token}. Right-padded
        batches read their logits at pos = len-1, so a causal model produces
        the same value as the unpadded forward. The flash path additionally
        threads per-row lengths so the kernel's kvlen block-skip does no work
        on padding (the XLA path needs no mask: causal right-padding is
        already exact, and leaving it unmasked keeps its HLO — and the
        hotpath bytes baselines — unchanged). ``remat`` recomputes each
        layer period in the backward pass instead of keeping its residuals.
        """
        flash = getattr(self.cfg, "attn_impl", "auto") == "flash"

        def f(e: jax.Array, aux: dict) -> jax.Array:
            lengths = aux["pos"] + 1 if flash else None
            h, _ = lm.hidden_from_embeds(
                self.cfg, params, e, lengths=lengths, remat=remat
            )
            rows = jnp.arange(e.shape[0])
            lg = lm.logits(self.cfg, params, h[rows, aux["pos"]]).astype(jnp.float32)
            return jax.nn.log_softmax(lg, axis=-1)[rows, aux["target"]]

        return f


def model_for(cfg):
    """Config -> model facade: ArchConfig -> Model, VitConfig -> VitModel.

    Both facades expose the explain-engine surface: ``init``,
    ``target_logprob_at_fn`` and an embedding hook (``embed_inputs`` for
    token models, ``embed_features`` for patch models).
    """
    if isinstance(cfg, ArchConfig):
        return Model(cfg)
    if getattr(cfg, "patch_size", 0):
        from repro.models.vit import VitModel

        return VitModel(cfg)
    raise TypeError(f"no model facade for config type {type(cfg).__name__}")


def input_specs(cfg: ArchConfig, shape: ShapeConfig, *, kv_slots: int = 0) -> dict:
    """ShapeDtypeStruct stand-ins for the step lowered by the dry-run."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.dtype(cfg.compute_dtype)
    sds = jax.ShapeDtypeStruct

    def frontend_spec():
        return sds((B, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model), f32)

    if shape.kind == "train":
        if cfg.frontend == "vision":
            s_text = S - cfg.frontend_tokens
            return {
                "tokens": sds((B, s_text), i32),
                "labels": sds((B, s_text), i32),
                "frontend": frontend_spec(),
            }
        if cfg.frontend == "audio":
            return {
                "tokens": sds((B, S), i32),
                "labels": sds((B, S), i32),
                "frontend": sds((B, cfg.encoder_seq, cfg.frontend_dim), f32),
            }
        return {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}

    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S if cfg.frontend != "vision" else S - cfg.frontend_tokens), i32)}
        if cfg.frontend == "vision":
            batch["frontend"] = frontend_spec()
        if cfg.frontend == "audio":
            batch["frontend"] = sds((B, cfg.encoder_seq, cfg.frontend_dim), f32)
        return batch

    # decode: one new token against a cache of seq_len
    cache = jax.eval_shape(
        partial(lm.init_cache, cfg, B, S, kv_slots=kv_slots)
    )
    if cfg.is_encdec:  # cross-KV entries exist after prefill; add them
        hd = cfg.resolved_head_dim
        kh = cfg.num_kv_heads
        xspec = sds((cfg.num_periods, B, cfg.encoder_seq, kh, hd), f32)

        def add_cross(layer_cache):
            lc = dict(layer_cache)
            lc["xk"] = xspec
            lc["xv"] = xspec
            return lc

        cache = dict(cache)
        cache["layers"] = tuple(add_cross(lc) for lc in cache["layers"])
        cache["rem"] = tuple(
            {**lc, "xk": sds((B, cfg.encoder_seq, kh, hd), f32),
             "xv": sds((B, cfg.encoder_seq, kh, hd), f32)}
            for lc in cache["rem"]
        )
    return {"token": sds((B, 1), i32), "cache": cache}
