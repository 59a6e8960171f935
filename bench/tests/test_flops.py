"""The FLOP counts against a hand count at the published widths."""
import pytest

from bench.harness import spec

BM = spec.load_benchmark()


def model_of(name):
    entry, sizes, model = spec.config_files(BM, name)
    return sizes, model


def test_vit_s16():
    c, m = model_of("vit-s16")
    S, d, f, L = 196, 384, 1536, 12
    qkv, out, mlp = 2 * S * d * 3 * d, 2 * S * d * d, 3 * 2 * S * d * f  # SwiGLU: 3 matrices
    scores = av = 2 * S * S * d
    head = 2 * d * 1000
    assert m.flops_forward(c, S) == L * (qkv + out + mlp + scores + av) + head
    assert m.flops_vjp(c, S) == L * (qkv + out + mlp + 2 * (scores + av)) + head
    assert m.flops_forward(c, S) == pytest.approx(11.806e9, rel=1e-3)
    assert m.flops_embed(c, S) == 2 * S * 768 * d
    # weights: 12 x (4 x 384^2 + 3 x 384 x 1536) + patch, position and head
    assert m.param_bytes(c) / 4 == 12 * (2 * d + 4 * d * d + 3 * d * f) + 768 * d + d + S * d \
        + d + d * 1000 + 1000


def test_mamba2_780m():
    c, m = model_of("mamba2-780m")
    d, di, N, H, P, V, L = 1536, 3072, 128, 48, 64, 50280, 48
    S = 192
    proj = 2 * S * d * (2 * di + 2 * N + H) + 2 * S * di * d
    conv = 2 * S * 4 * (di + 2 * N)
    pairs = S * (S + 1) // 2  # one chunk of 192 <= 256, causal (t, s) pairs
    ssd = pairs * (2 * N + 2 * H * P)
    logits = 2 * d * V
    assert m.flops_forward(c, S) == L * (proj + conv + ssd) + logits
    # input gradients: the projections again, the input-dependent products twice
    assert m.flops_vjp(c, S) == L * (proj + 2 * (conv + ssd)) + logits
    assert m.flops_forward(c, S) == pytest.approx(275.7e9, rel=2e-3)
    # ~780 M parameters at 4 bytes
    assert m.param_bytes(c) / 4 == pytest.approx(0.78e9, rel=0.03)


def test_mamba2_state_passes_between_chunks_past_256():
    c, m = model_of("mamba2-780m")
    one = m.flops_forward(c, 256)
    two = m.flops_forward(c, 512)
    # twice the projections, twice one chunk's pairs, plus the state passed on
    assert two > 2 * one - 2 * 2 * 1536 * 50280


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_an_explanation_counts_its_probe_or_endpoints(cell):
    """A paper-schedule explanation pays its n_int + 1 boundary probes, a
    uniform one its two endpoints, occlusion its masks and endpoints; each
    gradient step a forward and a VJP."""
    from types import SimpleNamespace

    from bench.harness import measure

    info = spec.cell(BM, cell)
    c, m, t = info["sizes"], info["model"], info["traffic"]
    ctx = measure.Ctx(c, m, t, {}, None, 0.0)
    S = 150 if m.KIND == "tokens" else 196
    result = {"token_scores": [0.0] * S}
    if t.get("adaptive"):
        result["m_used"] = 32  # a fixed-m answer carries no m_used
    r = SimpleNamespace(ticket=SimpleNamespace(result=result))
    fwd = m.flops_forward(c, S)
    if t["method"] == "occlusion":
        want = (t["n_masks"] + 2) * fwd
    else:
        probes = {"uniform": 2, "paper": t.get("n_int", 0) + 1}[t["schedule"]]
        steps = 32 if t.get("adaptive") else t["m"]
        want = probes * fwd + steps * (fwd + m.flops_vjp(c, S))
    assert ctx.explanation_flops(r) == pytest.approx(want + m.flops_embed(c, S))
