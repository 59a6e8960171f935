"""Flash attention op: model layout, ragged lengths, fused custom-VJP bwd.

``flash_attention`` is the explain-hot-path entry point: (B, S, H, D) model
layout in/out, optional per-row valid lengths, sequence padding to block
multiples (made exact by the kernel's kvlen mask + output slicing), and a
``jax.custom_vjp`` whose backward recomputes the probability tile from the
(B, NQ, Sq, 1) f32 logsumexp residual — differentiating through attention never
materializes the (B, H, S, S) score tensor in either direction.

Residuals kept for backward: q, k, v, o, lse, kvlen — O(B*S*H*D), vs the
O(B*H*S^2) score tensor the XLA materializing path saves.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import default_interpret
from repro.kernels.flash_attention.kernel import (
    flash_attention_bwd_dkv_pallas,
    flash_attention_bwd_dq_pallas,
    flash_attention_fwd_pallas,
)
from repro.kernels.flash_attention.ref import attention_ref, attention_vjp_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, kvlen, causal, block_q, block_k, interpret):
    o, _ = flash_attention_fwd_pallas(
        q, k, v, kvlen, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o


def _flash_fwd(q, k, v, kvlen, causal, block_q, block_k, interpret):
    o, lse = flash_attention_fwd_pallas(
        q, k, v, kvlen, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o, (q, k, v, o, lse, kvlen)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse, kvlen = res
    # softmax-jacobian diagonal term, shared by the dQ and dK/dV kernels
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    dq = flash_attention_bwd_dq_pallas(
        q, k, v, do, lse, delta, kvlen, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    dk, dv = flash_attention_bwd_dkv_pallas(
        q, k, v, do, lse, delta, kvlen, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    # integer lengths are non-differentiable: float0 cotangent
    return dq, dk, dv, np.zeros(kvlen.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_seq(x: jax.Array, mult: int) -> jax.Array:
    """Zero-pad the sequence axis (axis 2, kernel layout) to a multiple."""
    s = x.shape[2]
    pad = (-s) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def flash_attention(
    q: jax.Array,  # (B, S, NQ, D) — model layout
    k: jax.Array,  # (B, S, NKV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    lengths: Optional[jax.Array] = None,  # (B,) or (B, 1) valid K lengths
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable flash attention in model layout.

    ``interpret=None`` resolves via ``kernels.common.default_interpret``:
    interpreted on the CPU backend (CI), compiled on TPU. Sequence lengths
    that don't divide the block sizes are zero-padded; padded K positions
    are masked via kvlen so values and gradients match the unpadded oracle
    exactly, and padded Q rows are sliced off (their cotangent is zero, so
    they contribute nothing to dK/dV).
    """
    interpret = default_interpret(interpret)
    B, Sq, NQ, D = q.shape
    Sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    qt = _pad_seq(qt, bq)
    kt = _pad_seq(kt, bk)
    vt = _pad_seq(vt, bk)
    if lengths is None:
        kvlen = jnp.full((B, 1), Sk, jnp.int32)
    else:
        kvlen = jnp.minimum(lengths.astype(jnp.int32).reshape(B, 1), Sk)
    o = _flash(qt, kt, vt, kvlen, causal, bq, bk, interpret)
    return o[:, :, :Sq, :].transpose(0, 2, 1, 3)


__all__ = ["flash_attention", "attention_ref", "attention_vjp_ref"]
