"""Flash attention (GQA, causal, ragged) — Pallas TPU kernels, fwd + bwd.

TPU adaptation of the classic GPU algorithm: Q/K/V tiles are staged in VMEM
via BlockSpecs, the score tile hits the MXU (block sizes multiples of 128),
and the online-softmax running state (m, l, acc) lives in VMEM scratch across
the innermost (sequential) K-block grid dimension — replacing the GPU's
shared-memory/warp-register carries.

Forward grid: (B, NQ, Sq/bq, Sk/bk), K innermost. GQA: the K/V BlockSpec
index-maps query head h -> kv head h // G, so KV tiles are fetched once per
group. Fully-masked (future / beyond-kvlen) K blocks are skipped via pl.when
on the block index — with a causal grid this removes ~half the MXU work.

Backward pass (two kernels, independent tilings — see docs/attention.md):

* residuals are O and the per-row logsumexp ``lse = m + log(l)`` — the
  (bq, bk) probability tile is recomputed as ``exp(s - lse)`` instead of
  being materialized, so bwd memory is O(S*D) not O(S^2);
* ``delta = rowsum(dO * O)`` is precomputed once outside the kernels and
  shared by both (it is the softmax-jacobian diagonal term);
* dQ kernel: grid (B, NQ, Sq/bq, Sk/bk) K innermost, one (bq, D) f32 VMEM
  accumulator that stays resident across the K sweep;
* dK/dV kernel: grid (B, NKV, Sk/bk, G, Sq/bq) with the GQA group and the Q
  sweep innermost, so the (bk, D) f32 dK/dV accumulators for one KV tile
  stay resident while every query head of the group streams past.

Ragged masking: ``kvlen`` is a (B, 1) int32 of valid K lengths; K positions
>= kvlen[b] are masked in all kernels (this is also how the wrappers in
``ops.py`` make padded sequence lengths exact).

TPU block layout: a block's last two dims must be multiples of (8, 128) or
the full array dims. Q/K/V tiles squeeze the (batch, head) dims to (bq, D)
refs; the per-row f32 vectors (``lse``, ``delta``, the running max and
denominator) are (.., S, 1) columns, so a (bq, 1) tile is legal and
broadcasts against the (bq, bk) score tile with no relayout; ``kvlen`` is
read as a scalar from SMEM (the whole (B,) vector, indexed by program id).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(s, *, causal, qi, ki, bq, bk, kvlen):
    """Apply the causal + ragged-length mask to a (bq, bk) score tile."""
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = kpos < kvlen
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        keep &= qpos >= kpos
    return jnp.where(keep, s, NEG_INF), keep


def _tile(bs, D):
    """(B, H, S, D) block with (batch, head) squeezed -> a (bs, D) ref."""
    return (None, None, bs, D)


def _col(bs):
    """(B, H, S, 1) per-row f32 column block -> a (bs, 1) ref."""
    return (None, None, bs, 1)


_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)  # kvlen: whole (B,) vector


# ------------------------------------------------------------------ forward


def _flash_fwd_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, causal, bq, bk, scale,
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    nk = pl.num_programs(3)
    kvlen = kvlen_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip K blocks strictly in the future of this Q block or beyond kvlen
    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(live & (ki * bk < kvlen))
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[...].astype(jnp.float32)  # (bk, D)
        v = v_ref[...].astype(jnp.float32)  # (bk, D)
        s = q @ k.T  # (bq, bk) — MXU
        s, _ = _mask(s, causal=causal, qi=qi, ki=ki, bq=bq, bk=bk, kvlen=kvlen)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_fwd_pallas(
    q: jax.Array,  # (B, NQ, Sq, D)
    k: jax.Array,  # (B, NKV, Sk, D)
    v: jax.Array,
    kvlen: jax.Array,  # (B, 1) int32 valid K lengths
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (o, lse): the attention output and the (B, NQ, Sq, 1) f32
    per-row logsumexp residual the backward kernels recompute P from."""
    B, NQ, Sq, D = q.shape
    NKV, Sk = k.shape[1], k.shape[2]
    G = NQ // NKV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    grid = (B, NQ, Sq // bq, Sk // bk)
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, bq=bq, bk=bk, scale=D**-0.5
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _SMEM_WHOLE,
            pl.BlockSpec(_tile(bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(_tile(bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec(_tile(bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec(_tile(bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(_col(bq), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NQ, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, NQ, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max m
            pltpu.VMEM((bq, 1), jnp.float32),  # running denom l
            pltpu.VMEM((bq, D), jnp.float32),  # running output acc
        ],
        interpret=interpret,
    )(kvlen.reshape(B), q, k, v)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,  # (B, NQ, Sq, D)
    k: jax.Array,  # (B, NKV, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    B, Sk = q.shape[0], k.shape[2]
    kvlen = jnp.full((B, 1), Sk, jnp.int32)
    o, _ = flash_attention_fwd_pallas(
        q, k, v, kvlen, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o


# ----------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, causal, bq, bk, scale,
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    nk = pl.num_programs(3)
    kvlen = kvlen_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(live & (ki * bk < kvlen))
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (bq, D)
        k = k_ref[...].astype(jnp.float32)  # (bk, D)
        v = v_ref[...].astype(jnp.float32)  # (bk, D)
        do = do_ref[...].astype(jnp.float32)  # (bq, D)
        lse = lse_ref[...]  # (bq, 1) f32
        delta = delta_ref[...]  # (bq, 1) f32
        s = (q @ k.T) * scale
        _, keep = _mask(s, causal=causal, qi=qi, ki=ki, bq=bq, bk=bk, kvlen=kvlen)
        # recompute P from the lse residual; explicit zero (not exp(NEG_INF -
        # lse)) so fully-masked rows with lse ~ NEG_INF stay exactly zero
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = do @ v.T  # (bq, bk)
        ds = p * (dp - delta)
        acc_ref[...] += ds @ k

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_bwd_dq_pallas(
    q: jax.Array,  # (B, NQ, Sq, D)
    k: jax.Array,  # (B, NKV, Sk, D)
    v: jax.Array,
    do: jax.Array,  # (B, NQ, Sq, D) output cotangent
    lse: jax.Array,  # (B, NQ, Sq, 1) f32 forward residual
    delta: jax.Array,  # (B, NQ, Sq, 1) f32 rowsum(dO * O)
    kvlen: jax.Array,  # (B, 1) int32
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    B, NQ, Sq, D = q.shape
    NKV, Sk = k.shape[1], k.shape[2]
    G = NQ // NKV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    grid = (B, NQ, Sq // bq, Sk // bk)
    kernel = functools.partial(
        _flash_bwd_dq_kernel, causal=causal, bq=bq, bk=bk, scale=D**-0.5
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _SMEM_WHOLE,
            pl.BlockSpec(_tile(bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(_tile(bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec(_tile(bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec(_tile(bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(_col(bq), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(_col(bq), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec(_tile(bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, NQ, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],  # dq accumulator
        interpret=interpret,
    )(kvlen.reshape(B), q, k, v, do, lse, delta)


def _flash_bwd_dkv_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, causal, bq, bk, scale,
):
    jk = pl.program_id(2)
    g = pl.program_id(3)
    qi = pl.program_id(4)
    ng = pl.num_programs(3)
    nq = pl.num_programs(4)
    kvlen = kvlen_ref[pl.program_id(0)]

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # skip Q blocks strictly before this K block (causal) or dead K blocks
    live = (qi * bq + bq - 1 >= jk * bk) if causal else (qi >= 0)

    @pl.when(live & (jk * bk < kvlen))
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (bq, D)
        k = k_ref[...].astype(jnp.float32)  # (bk, D)
        v = v_ref[...].astype(jnp.float32)  # (bk, D)
        do = do_ref[...].astype(jnp.float32)  # (bq, D)
        lse = lse_ref[...]  # (bq, 1) f32
        delta = delta_ref[...]  # (bq, 1) f32
        s = (q @ k.T) * scale
        _, keep = _mask(s, causal=causal, qi=qi, ki=jk, bq=bq, bk=bk, kvlen=kvlen)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dv_acc[...] += p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta)
        dk_acc[...] += ds.T @ q

    @pl.when((g == ng - 1) & (qi == nq - 1))
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_bwd_dkv_pallas(
    q: jax.Array,  # (B, NQ, Sq, D)
    k: jax.Array,  # (B, NKV, Sk, D)
    v: jax.Array,
    do: jax.Array,  # (B, NQ, Sq, D)
    lse: jax.Array,  # (B, NQ, Sq, 1) f32
    delta: jax.Array,  # (B, NQ, Sq, 1) f32
    kvlen: jax.Array,  # (B, 1) int32
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    B, NQ, Sq, D = q.shape
    NKV, Sk = k.shape[1], k.shape[2]
    G = NQ // NKV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    # group g and the Q sweep are the two innermost (sequential) dims so the
    # (bk, D) dK/dV accumulators stay VMEM-resident for one KV tile
    grid = (B, NKV, Sk // bk, G, Sq // bq)
    kernel = functools.partial(
        _flash_bwd_dkv_kernel, causal=causal, bq=bq, bk=bk, scale=D**-0.5
    )
    kv_index = lambda b, hk, jk, g, iq: (b, hk, jk, 0)  # noqa: E731
    q_index = functools.partial(_q_index, G=G)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _SMEM_WHOLE,
            pl.BlockSpec(_tile(bq, D), q_index),
            pl.BlockSpec(_tile(bk, D), kv_index),
            pl.BlockSpec(_tile(bk, D), kv_index),
            pl.BlockSpec(_tile(bq, D), q_index),
            pl.BlockSpec(_col(bq), q_index),
            pl.BlockSpec(_col(bq), q_index),
        ],
        out_specs=[
            pl.BlockSpec(_tile(bk, D), kv_index),
            pl.BlockSpec(_tile(bk, D), kv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NKV, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, NKV, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),  # dk accumulator
            pltpu.VMEM((bk, D), jnp.float32),  # dv accumulator
        ],
        interpret=interpret,
    )(kvlen.reshape(B), q, k, v, do, lse, delta)


def _q_index(b, hk, jk, g, iq, *, G):
    return (b, hk * G + g, iq, 0)
