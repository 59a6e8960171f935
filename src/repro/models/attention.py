"""GQA attention: full, blocked (online-softmax), sliding-window, decode.

Layouts:  q (B, S, NQ, D)   k/v (B, S, NKV, D)   grouped as NQ = NKV * G.
The blocked paths never materialize an (S, S) score matrix — they are the
pure-jnp counterpart of the Pallas flash kernel in ``repro.kernels``, which
``attn_impl="flash"`` selects. The kernel runs compiled on a TPU and in
interpret mode on the CPU test backend (``kernels.common.default_interpret``);
``tests/test_tpu_compile.py`` compiles it for a described v5e chip.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParamDef
from repro.models import common as _common
from repro.sharding.context import constrain
from repro.models.layers import rope

NEG_INF = -1e30


def attn_def(cfg: ArchConfig, *, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, cfg.num_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((cfg.num_heads, hd, d), ("heads", "head_dim", "embed")),
    }


def qkv(p: dict, x: jax.Array, dtype) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype))
    # pin heads on TP axis (kv heads fall back to replicated if indivisible)
    q = constrain(q, "batch", "seq", "model", None)
    k = constrain(k, "batch", "seq", "model", None)
    v = constrain(v, "batch", "seq", "model", None)
    return q, k, v


def out_proj(p: dict, o: jax.Array, dtype) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dtype))


def _group(q: jax.Array, nkv: int) -> jax.Array:
    """(B, S, NQ, D) -> (B, S, NKV, G, D)."""
    B, S, NQ, D = q.shape
    return q.reshape(B, S, nkv, NQ // nkv, D)


# ------------------------------------------------------------- full (small S)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    window: int = 0,
    kv_len: Optional[jax.Array] = None,  # (B,) valid K lengths (ragged batch)
) -> jax.Array:
    """Reference einsum attention; materializes (Sq, Sk) scores. Small-S path.

    GQA K/V are EXPANDED to the full Q-head count before the einsum. The
    grouped (B,S,kv,G,D) layout looks cheaper but is a TP trap: with kv=8 or
    G=4 on a 16-way 'model' axis neither head factor is divisible, so the
    SPMD partitioner replicates attention over the model axis (measured 16x
    flops/chip on llama3 train_4k). With the expanded layout the head axis
    shards cleanly; XLA fuses the repeat into the matmul operand load.
    """
    B, Sq, NQ, D = q.shape
    nkv = k.shape[2]
    ke, ve = expand_kv(k, NQ), expand_kv(v, NQ)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * (D**-0.5), ke).astype(jnp.float32)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    if kv_len is not None:  # per-row ragged mask: (B, 1, Sq, Sk)
        valid = kpos[None, :] < kv_len.reshape(-1, 1)  # (B, Sk)
        full = mask[None, None] & valid[:, None, None, :]
        s = jnp.where(full, s, NEG_INF)
    else:
        s = jnp.where(mask, s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, ve)
    return o


# ----------------------------------------------------- blocked online-softmax


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Flash-style attention in pure jnp: lax.map over Q blocks, lax.scan over
    K blocks with running (max, sum, acc). Peak memory O(block_q * block_k)."""
    B, Sq, NQ, D = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    ke, ve = expand_kv(k, NQ), expand_kv(v, NQ)  # TP-shardable head axis
    qb = q.reshape(B, nq, bq, NQ, D).swapaxes(0, 1)  # (nq, B, bq, NQ, D)
    kb = ke.reshape(B, nk, bk, NQ, D).swapaxes(0, 1)
    vb = ve.reshape(B, nk, bk, NQ, D).swapaxes(0, 1)
    scale = D**-0.5

    def q_block(args):
        qi, qblk = args  # scalar index, (B, bq, NQ, D)
        qs = qblk * scale

        def kv_step(carry, xs):
            m, l, acc = carry
            ki, kblk, vblk = xs
            s = jnp.einsum("bqhd,bkhd->bhqk", qs, kblk).astype(jnp.float32)
            if causal:
                qpos = qi * bq + jnp.arange(bq)
                kpos = ki * bk + jnp.arange(bk)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        shape = (B, NQ, bq)
        init = (
            jnp.full(shape, NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (D,), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (jnp.arange(nk), kb, vb))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return o.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, bq, NQ, D)

    out = jax.lax.map(q_block, (jnp.arange(nq), qb))  # (nq, B, bq, NQ, D)
    return out.swapaxes(0, 1).reshape(B, Sq, NQ, D)


# -------------------------------------------------------------- sliding window


def local_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, window: int
) -> jax.Array:
    """Causal sliding-window attention, vectorized over window-sized blocks.

    Each Q block attends its own block + the previous block with a band mask:
    compute is O(S * 2w) instead of O(S^2).
    """
    B, S, NQ, D = q.shape
    w = window
    if S <= 2 * w:  # small sequences: mask path is cheaper than blocking
        return full_attention(q, k, v, causal=True, window=w)
    assert S % w == 0, (S, w)
    nb = S // w
    kx, vx = expand_kv(k, NQ), expand_kv(v, NQ)  # TP-shardable head axis
    qb = q.reshape(B, nb, w, NQ, D) * (D**-0.5)

    def ext(x):  # (B, S, H, D) -> (B, nb, 2w, H, D): [prev block | own block]
        xb = x.reshape(B, nb, w, NQ, D)
        prev = jnp.concatenate([jnp.zeros_like(xb[:, :1]), xb[:, :-1]], axis=1)
        return jnp.concatenate([prev, xb], axis=2)

    ke, ve = ext(kx), ext(vx)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, ke).astype(jnp.float32)
    qpos = jnp.arange(w)[:, None]
    kpos = jnp.arange(2 * w)[None, :] - w  # relative to block start
    mask = (qpos >= kpos) & (qpos - kpos < w)  # causal & within window
    first = jnp.arange(nb) == 0  # first block has no prev block
    mask = jnp.where(first[:, None, None], mask & (kpos >= 0), mask)  # (nb, w, 2w)
    s = jnp.where(mask[None, :, None], s, NEG_INF)  # align to (B, nb, h, q, k)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bnhqk,bnkhd->bnqhd", a, ve)
    return o.reshape(B, S, NQ, D)


# ------------------------------------------------------------------- decode


def decode_attention(
    q: jax.Array,  # (B, 1, NQ, D)
    k_cache: jax.Array,  # (B, Smax, KH, D)  (KH may be TP-expanded)
    v_cache: jax.Array,
    cache_len: jax.Array,  # () current valid length (== new token position + 1)
    *,
    window: int = 0,
    ring: bool = False,
) -> jax.Array:
    """Single-token attention against a (possibly ring-buffer) KV cache.

    Expanded-KV head layout (see full_attention): the cache may already be
    TP-expanded via ``kv_slots``; any remaining group factor is expanded
    here so the head axis stays shardable.
    """
    B, Smax, KH, D = k_cache.shape
    NQ = q.shape[2]
    ke, ve = expand_kv(k_cache, NQ), expand_kv(v_cache, NQ)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * (D**-0.5), ke).astype(jnp.float32)
    idx = jnp.arange(Smax)
    if ring:
        valid = idx < jnp.minimum(cache_len, Smax)  # ring: whole buffer once full
    else:
        valid = idx < cache_len
        if window:
            valid &= idx >= cache_len - window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, ve)
    return o


def expand_kv(k: jax.Array, target_heads: int) -> jax.Array:
    """Repeat KV heads so the cache head axis is shardable by TP.

    GQA configs have 4–16 KV heads but the 'model' mesh axis is 16; repeating
    KV heads to ``target_heads`` slots lets each TP shard hold exactly the KV
    group its Q heads consume (4x less memory than full replication).
    """
    B, S, KH, D = k.shape
    if KH >= target_heads:
        return k
    rep = target_heads // KH
    return jnp.repeat(k, rep, axis=2)


def dispatch_attention(
    cfg: ArchConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mixer: str,
    causal: bool,
    kv_len: Optional[jax.Array] = None,  # (B,) ragged valid K lengths
    block_threshold: int = 4096,
) -> jax.Array:
    """Pick the attention algorithm for a (layer kind, seq length) pair.

    ``cfg.attn_impl == "flash"`` routes full-attention layers through the
    Pallas kernel (custom-VJP backward, no (B, H, S, S) score tensor in
    either direction); everything else stays on the XLA paths. Costing mode
    always materializes: Pallas flops/bytes are invisible to cost_analysis.
    """
    S = q.shape[1]
    if mixer == "local" and cfg.sliding_window:
        return local_attention(q, k, v, window=cfg.sliding_window)
    if _common.COSTING:  # costing mode: straight-line HLO, same flops
        return full_attention(q, k, v, causal=causal, kv_len=kv_len)
    if getattr(cfg, "attn_impl", "auto") == "flash":
        from repro.kernels.flash_attention.ops import flash_attention

        return flash_attention(
            q, k, v, causal=causal, lengths=kv_len,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        )
    if S > block_threshold and kv_len is None:
        return blocked_attention(q, k, v, causal=causal)
    return full_attention(q, k, v, causal=causal, kv_len=kv_len)
