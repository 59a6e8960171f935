"""Per-arch smoke tests: reduced config, one forward + one train step on CPU,
output shapes + no NaNs. Attention algorithm equivalences. Serving parity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import attention as attn
from repro.models.registry import Model

KEY = jax.random.PRNGKey(0)
ARCH_IDS = sorted(ARCHS)


def _make_batch(cfg, B=2, S=32, key=KEY):
    k1, k2 = jax.random.split(key)
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
    }
    if cfg.frontend == "vision":
        batch["frontend"] = jnp.ones((B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
    if cfg.frontend == "audio":
        batch["frontend"] = jnp.ones((B, cfg.encoder_seq, cfg.frontend_dim), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad_no_nans(arch):
    cfg = reduced(ARCHS[arch])
    model = Model(cfg)
    params = model.init(KEY)
    batch = _make_batch(cfg)
    loss = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch
    grads = jax.grad(lambda p: model.loss(p, batch))(params)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_hidden_shapes(arch):
    cfg = reduced(ARCHS[arch])
    model = Model(cfg)
    params = model.init(KEY)
    batch = _make_batch(cfg, B=2, S=16)
    h, aux = model.forward_hidden(params, batch)
    S_expect = 16 + (cfg.frontend_tokens if cfg.frontend == "vision" else 0)
    assert h.shape == (2, S_expect, cfg.d_model)
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    """Teacher-forced decode logits == full-sequence forward logits.

    Run in float32: this pins cache SEMANTICS (prefill->decode handoff);
    bf16 rounds the two computation orders differently (SSM state carries
    ~0.2 logit noise) without any algorithmic divergence.
    """
    import dataclasses

    cfg = dataclasses.replace(reduced(ARCHS[arch]), compute_dtype="float32")
    model = Model(cfg)
    params = model.init(KEY)
    B, S = 2, 16
    batch = _make_batch(cfg, B=B, S=S)
    batch.pop("labels")

    h, _ = model.forward_hidden(params, {**batch, "labels": None} if False else batch)
    full_logits = np.asarray(model.logits(params, h).astype(jnp.float32))

    text_off = cfg.frontend_tokens if cfg.frontend == "vision" else 0
    # cache must cover prepended frontend tokens + the decoded continuation
    lg, cache = model.prefill(params, batch, max_len=text_off + S + 8)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0].astype(jnp.float32)),
        full_logits[:, -1],
        rtol=5e-2,
        atol=5e-2,
    )
    # decode 4 tokens teacher-forced against an extended forward pass
    extra = jax.random.randint(jax.random.fold_in(KEY, 7), (B, 4), 0, cfg.vocab_size)
    toks = jnp.concatenate([batch["tokens"], extra], axis=1)
    h2, _ = model.forward_hidden(params, {**batch, "tokens": toks})
    want = np.asarray(model.logits(params, h2).astype(jnp.float32))
    for i in range(4):
        lg, cache = model.decode_step(params, cache, extra[:, i : i + 1])
        got = np.asarray(lg[:, 0].astype(jnp.float32))
        np.testing.assert_allclose(
            got, want[:, text_off + S + i], rtol=5e-2, atol=8e-2
        ), f"{arch} step {i}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_embedding_space_ig_hook(arch):
    """target_logprob_fn is differentiable wrt embeddings for every arch."""
    cfg = reduced(ARCHS[arch])
    model = Model(cfg)
    params = model.init(KEY)
    batch = _make_batch(cfg, B=2, S=8)
    e = model.embed_inputs(params, batch)
    f = model.target_logprob_fn(params)
    t = jnp.zeros((2,), jnp.int32)
    val = f(e, t)
    assert val.shape == (2,)
    g = jax.grad(lambda ee: f(ee, t).sum())(e)
    assert g.shape == e.shape
    assert bool(jnp.all(jnp.isfinite(g)))


def test_attention_blocked_equals_full():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 16))
    k = jax.random.normal(ks[1], (2, 128, 2, 16))
    v = jax.random.normal(ks[2], (2, 128, 2, 16))
    full = attn.full_attention(q, k, v, causal=True)
    blocked = attn.blocked_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(blocked), rtol=2e-3, atol=2e-4)


def test_attention_local_equals_masked_full():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    w = 16
    local = attn.local_attention(q, k, v, window=w)
    masked = attn.full_attention(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(local), np.asarray(masked), rtol=2e-3, atol=2e-4)


def test_decode_attention_equals_full_tail():
    ks = jax.random.split(KEY, 4)
    S = 32
    q = jax.random.normal(ks[0], (1, 1, 4, 16))
    kc = jax.random.normal(ks[1], (1, S, 2, 16))
    vc = jax.random.normal(ks[2], (1, S, 2, 16))
    L = 20  # valid cache length
    got = attn.decode_attention(q, kc, vc, jnp.asarray(L))
    want = attn.full_attention(
        q, kc[:, :L], vc[:, :L], causal=True, q_offset=L - 1
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-4)


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor >= k*E/E the drop rate stays small on random data."""
    from repro.models.moe import moe, moe_def
    from repro.models.common import init_params

    cfg = reduced(ARCHS["qwen3-moe-30b-a3b"])
    p = init_params(KEY, moe_def(cfg))
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 64, cfg.d_model)).astype(jnp.bfloat16)
    y, aux = moe(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))
    assert float(aux) >= 0.0


def test_ssm_chunked_matches_small_chunk():
    """SSD chunked scan result is chunk-size invariant."""
    import dataclasses
    from repro.models import ssm

    cfg = reduced(ARCHS["mamba2-780m"])
    p_defs = ssm.ssm_def(cfg)
    from repro.models.common import init_params

    p = init_params(KEY, p_defs)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 32, cfg.d_model)).astype(jnp.float32)
    y1 = ssm.ssm_forward(p, x, cfg)
    cfg2 = dataclasses.replace(cfg, ssm_chunk=8)
    y2 = ssm.ssm_forward(p, x, cfg2)
    np.testing.assert_allclose(
        np.asarray(y1, np.float32), np.asarray(y2, np.float32), rtol=2e-2, atol=2e-3
    )


def test_ssm_grad_finite_at_published_chunk():
    """At the published 256-step chunk the masked (upper) intra-chunk decay
    exponents exceed f32's exp range; the input gradient must stay finite
    (it is what every attribution of an SSM differentiates)."""
    import dataclasses
    from repro.models import ssm
    from repro.models.common import init_params

    cfg = dataclasses.replace(reduced(ARCHS["mamba2-780m"]), ssm_chunk=256)
    p = init_params(KEY, ssm.ssm_def(cfg))
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 256, cfg.d_model))
    g = jax.grad(lambda x: ssm.ssm_forward(p, x, cfg).astype(jnp.float32).sum())(x)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_param_count_analytic_matches_materialized():
    """ArchConfig.param_count (roofline input) == actual leaf count."""
    for arch in ("llama3-8b", "qwen3-moe-30b-a3b", "mamba2-780m", "whisper-tiny"):
        cfg = reduced(ARCHS[arch])
        model = Model(cfg)
        params = model.init(KEY)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert abs(actual - cfg.param_count()) / actual < 0.02, arch
