"""Mamba-2 (SSD — state-space duality) block: chunked train/prefill path +
single-step decode recurrence.

TPU adaptation: the SSD chunked algorithm is already MXU-shaped (intra-chunk
work is batched matmuls). Intra-chunk terms are computed for ALL chunks at
once (chunk axis = batch axis), and the inter-chunk state recurrence is a
log-depth ``lax.associative_scan`` — fully parallel on TPU, unlike the
sequential per-chunk lax.scan a straight GPU port would use. Nothing O(S^2)
is ever materialized; SSD heads shard on the 'model' mesh axis (head-parallel
== TP for SSMs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParamDef
from repro.sharding.context import constrain


def ssm_def(cfg: ArchConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    G, N, H, W = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    return {
        "in_z": ParamDef((d, di), ("embed", "inner")),
        "in_x": ParamDef((d, di), ("embed", "inner")),
        "in_B": ParamDef((d, G * N), ("embed", None)),
        "in_C": ParamDef((d, G * N), ("embed", None)),
        "in_dt": ParamDef((d, H), ("embed", "ssm_heads")),
        "conv_x": ParamDef((W, di), (None, "inner"), scale=0.5),
        "conv_B": ParamDef((W, G * N), (None, None), scale=0.5),
        "conv_C": ParamDef((W, G * N), (None, None), scale=0.5),
        "A_log": ParamDef((H,), ("ssm_heads",), init="zeros"),
        "D": ParamDef((H,), ("ssm_heads",), init="ones"),
        "dt_bias": ParamDef((H,), ("ssm_heads",), init="zeros"),
        "norm": ParamDef((di,), ("inner",), init="ones"),
        "out": ParamDef((di, d), ("inner", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifted adds (width is tiny, e.g. 4)."""
    W = w.shape[0]
    out = x * w[-1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[W - 1 - i]
    return out


def _gated_norm(scale: jax.Array, y: jax.Array, z: jax.Array, eps: float) -> jax.Array:
    g = y * jax.nn.silu(z)
    g32 = g.astype(jnp.float32)
    var = jnp.mean(jnp.square(g32), axis=-1, keepdims=True)
    return (g32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def _proj_inputs(p: dict, u: jax.Array, cfg: ArchConfig):
    dt_ = u.dtype
    z = jnp.einsum("bsd,de->bse", u, p["in_z"].astype(dt_))
    x = jnp.einsum("bsd,de->bse", u, p["in_x"].astype(dt_))
    Bm = jnp.einsum("bsd,de->bse", u, p["in_B"].astype(dt_))
    Cm = jnp.einsum("bsd,de->bse", u, p["in_C"].astype(dt_))
    dt = jnp.einsum("bsd,dh->bsh", u, p["in_dt"].astype(dt_))
    return z, x, Bm, Cm, dt


def ssm_forward(p: dict, u: jax.Array, cfg: ArchConfig, eps: float = 1e-6) -> jax.Array:
    """Full-sequence SSD. u: (B, S, d_model) -> (B, S, d_model)."""
    return _ssd(p, u, cfg, eps, return_state=False)


def ssm_forward_with_state(
    p: dict, u: jax.Array, cfg: ArchConfig, eps: float = 1e-6
) -> tuple[jax.Array, dict]:
    """Prefill: full-sequence SSD returning the decode cache (state + conv tail)."""
    return _ssd(p, u, cfg, eps, return_state=True)


def _ssd(p: dict, u: jax.Array, cfg: ArchConfig, eps: float, return_state: bool):
    Bb, S, _ = u.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    # largest chunk <= cfg.ssm_chunk that divides S (SSD is exact for any
    # chunking; odd prefill lengths just get slightly smaller chunks)
    cl = min(cfg.ssm_chunk, S)
    while S % cl:
        cl -= 1
    nc = S // cl

    z, x, Bm, Cm, dt = _proj_inputs(p, u, cfg)
    raw_xbc = jnp.concatenate([x, Bm, Cm], axis=-1) if return_state else None
    x = jax.nn.silu(_causal_conv(x, p["conv_x"].astype(x.dtype)))
    Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B"].astype(x.dtype)))
    Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C"].astype(x.dtype)))

    xh = constrain(x.reshape(Bb, S, H, P), "batch", "seq", "model", None)
    rep = H // G
    Bh = jnp.repeat(Bm.reshape(Bb, S, G, N), rep, axis=2)  # (B, S, H, N)
    Ch = jnp.repeat(Cm.reshape(Bb, S, G, N), rep, axis=2)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    # chunked layout: (nc, B, cl, ...)
    def chunked(t):
        return t.reshape(Bb, nc, cl, *t.shape[2:]).swapaxes(0, 1)

    xc, Bc, Cc, dtc = map(chunked, (xh, Bh, Ch, dt))
    dA = dtc * A  # (nc, B, cl, H) fp32
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative decay

    # ---- intra-chunk (diag) term, batched over ALL chunks (no scan):
    # L[l, s] = exp(cum_l - cum_s), causal within the chunk.
    L = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (nc, B, l, s, H)
    l_idx = jnp.arange(cl)
    causal = l_idx[:, None] >= l_idx[None, :]
    # mask BEFORE the exp: above the diagonal cum_l - cum_s > 0 grows with
    # the chunk (about 177 at chunk 256 with unit decay), exp overflows to
    # inf, and a where after the exp turns that into 0 * inf = NaN in the
    # backward pass. exp(-inf) is 0 with a zero gradient.
    L = jnp.exp(jnp.where(causal[None, None, :, :, None], L, -jnp.inf))
    xdt = xc.astype(jnp.float32) * (dA / A)[..., None]  # x*dt (dA = dt*A)
    Cf, Bf = Cc.astype(jnp.float32), Bc.astype(jnp.float32)
    y_diag = jnp.einsum("cblhn,cbshn,cblsh,cbshp->cblhp", Cf, Bf, L, xdt)

    # ---- per-chunk state contribution and decay (still no scan)
    in_decay = jnp.exp(cum[:, :, -1:, :] - cum)  # (nc, B, l, H)
    new_contrib = jnp.einsum("cblhn,cblh,cblhp->cbhpn", Bf, in_decay, xdt)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (nc, B, H)

    # ---- inter-chunk state recurrence: s_k = s_{k-1} * d_k + c_k.
    # Log-depth associative scan over chunks — parallel on TPU (vs the
    # sequential lax.scan a straight port would use) and visible in full to
    # HLO cost analysis (no while loop).
    def combine(lhs, rhs):
        d_l, c_l = lhs
        d_r, c_r = rhs
        return d_l * d_r, c_l * d_r[..., None, None] + c_r

    ds, cs = jax.lax.associative_scan(combine, (chunk_decay, new_contrib), axis=0)
    final_state = cs[-1]
    states_in = jnp.concatenate(
        [jnp.zeros_like(cs[:1]), cs[:-1]], axis=0
    )  # state entering chunk k (exclusive scan)

    out_decay = jnp.exp(cum)  # (nc, B, l, H)
    y_off = jnp.einsum("cblhn,cbhpn,cblh->cblhp", Cf, states_in, out_decay)

    yc = (y_diag + y_off).astype(xc.dtype)
    y = yc.swapaxes(0, 1).reshape(Bb, S, H, P)
    y = y + xh * p["D"].astype(xh.dtype)[None, None, :, None]
    y = y.reshape(Bb, S, H * P)
    y = _gated_norm(p["norm"], y, z, eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out"].astype(y.dtype))
    if not return_state:
        return out
    W = cfg.ssm_conv
    tail = raw_xbc[:, max(S - (W - 1), 0) :]
    if S < W - 1:  # left-pad with zeros to W-1 entries
        tail = jnp.pad(tail, ((0, 0), (W - 1 - S, 0), (0, 0)))
    return out, {"state": final_state, "conv": tail}


# ------------------------------------------------------------------- decode


def ssm_init_cache(cfg: ArchConfig, batch: int, dtype) -> dict:
    H, P, N, G, W = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv
    ch = cfg.d_inner + 2 * G * N
    return {
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, W - 1, ch), dtype),  # last W-1 conv inputs
    }


def ssm_decode_step(
    p: dict, u: jax.Array, cache: dict, cfg: ArchConfig, eps: float = 1e-6
) -> tuple[jax.Array, dict]:
    """u: (B, 1, d_model); single-token recurrent update."""
    Bb = u.shape[0]
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    di = cfg.d_inner
    z, x, Bm, Cm, dt = _proj_inputs(p, u, cfg)
    xbc = jnp.concatenate([x, Bm, Cm], axis=-1)[:, 0]  # (B, ch)
    hist = jnp.concatenate([cache["conv"], xbc[:, None]], axis=1)  # (B, W, ch)
    wfull = jnp.concatenate(
        [p["conv_x"], p["conv_B"], p["conv_C"]], axis=-1
    ).astype(xbc.dtype)  # (W, ch)
    conv_out = jnp.einsum("bwc,wc->bc", hist, wfull)
    conv_out = jax.nn.silu(conv_out)
    x = conv_out[:, :di]
    Bm = conv_out[:, di : di + G * N]
    Cm = conv_out[:, di + G * N :]

    xh = x.reshape(Bb, H, P).astype(jnp.float32)
    rep = H // G
    Bh = jnp.repeat(Bm.reshape(Bb, G, N), rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Cm.reshape(Bb, G, N), rep, axis=1).astype(jnp.float32)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    decay = jnp.exp(dtv * A)  # (B, H)
    state = cache["state"] * decay[..., None, None] + jnp.einsum(
        "bhp,bhn,bh->bhpn", xh, Bh, dtv
    )
    y = jnp.einsum("bhn,bhpn->bhp", Ch, state)  # (B, H, P)
    y = y + xh * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(Bb, 1, H * P).astype(u.dtype)
    y = _gated_norm(p["norm"], y, z, eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out"].astype(y.dtype))
    new_cache = {"state": state, "conv": hist[:, 1:]}
    return out, new_cache
