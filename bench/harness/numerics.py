"""Precision modes of the plain references.

``f32``  the reference: float32 everywhere, matmuls at ``HIGHEST``
         precision (on a TPU the default float32 matmul is one bf16 pass).
``bf16`` a control: weights and activations in bfloat16, norms and softmax
         in float32, as a bfloat16 model keeps them.
``fp8``  a control: as ``bf16``, with every weight matmul's operands
         rounded to float8 e4m3 under a per-tensor absmax scale.

A control is the reference computed one precision below what a
configuration states; ``correct`` must come out false for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
_E4M3_MAX = 448.0


def act_dtype(mode: str):
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _fp8(t: jax.Array) -> jax.Array:
    t32 = t.astype(jnp.float32)
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(t32)), 1e-30) / _E4M3_MAX)
    q = (t32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def mm(mode: str, subscripts: str, a: jax.Array, w: jax.Array) -> jax.Array:
    """An activation-by-weight contraction in the mode's precision."""
    if mode == "f32":
        return jnp.einsum(subscripts, a.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.einsum(subscripts, a.astype(jnp.bfloat16), w.astype(jnp.bfloat16))


def act_mm(mode: str, subscripts: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """An activation-by-activation contraction (attention scores, SSD):
    float32 at HIGHEST in the reference, bfloat16 operands otherwise."""
    if mode == "f32":
        return jnp.einsum(subscripts, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(subscripts, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)
