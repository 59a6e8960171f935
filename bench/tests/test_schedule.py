"""The reference's plain schedule gives the nodes the program's does."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import schedule as sch
from repro.core import schedule as prog


def probe_values(seed):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(size=5) ** 3)


@pytest.mark.parametrize("seed", range(8))
def test_paper_schedule_and_first_refinement_match_the_program(seed):
    vals = probe_values(seed)
    alloc = sch.paper_alloc(vals, 16)
    assert alloc.sum() == 16 and alloc.min() >= 1
    p = prog.paper(jnp.asarray(vals[None], jnp.float32), 16)
    a, w = sch.from_alloc(alloc, 16)
    np.testing.assert_allclose(a, np.asarray(p.alphas[0]), atol=1e-6)
    np.testing.assert_allclose(w, np.asarray(p.weights[0]), atol=1e-7)
    p = prog.refine_nested(p)
    a, w = sch.rung(alloc, 16, 32)
    np.testing.assert_allclose(a, np.asarray(p.alphas[0]), atol=1e-5)
    np.testing.assert_allclose(w, np.asarray(p.weights[0]), atol=1e-7)
    assert w.sum() == pytest.approx(1.0)


def test_higher_rungs_match_up_to_ties():
    """From rung 64 on, refinement ties (children on cell edges) can fall
    either way with the last bit of the arithmetic; the program's node set
    is among the reference's float64 and float32 variants for most
    allocations, and the comparison takes the variant nearest the answer."""
    n, hits = 200, 0
    for seed in range(n):
        vals = probe_values(seed)
        alloc = sch.paper_alloc(vals, 16)
        p = prog.paper(jnp.asarray(vals[None], jnp.float32), 16)
        for _ in range(3):
            p = prog.refine_nested(p)
        got = np.asarray(p.alphas[0])
        hits += any(np.abs(a - got).max() < 1e-4 for a, _ in sch.variants(alloc, 16, 128))
        for a, w in sch.variants(alloc, 16, 128):
            assert w.sum() == pytest.approx(1.0) and a.min() >= 0 and a.max() <= 1
    assert hits >= 0.85 * n


@pytest.mark.parametrize("m", [16, 32])
def test_uniform_schedule_matches_the_program(m):
    """A uniform schedule is one interval of midpoint nodes, refined as the
    program refines it."""
    u = prog.uniform(16)
    s = prog.Schedule(u.alphas[None], u.weights[None])
    while s.alphas.shape[1] < m:
        s = prog.refine_nested(s)
    a, w = sch.rung(np.array([16]), 16, m)
    np.testing.assert_allclose(a, np.asarray(s.alphas[0]), atol=1e-6)
    np.testing.assert_allclose(w, np.asarray(s.weights[0]), atol=1e-7)


@pytest.mark.parametrize("seed", range(4))
def test_ties_start_at_the_tie_rung(seed):
    """Below ``TIE_RUNG`` float32 and float64 refinement give one node set,
    so the comparison there evaluates the reference once."""
    alloc = sch.paper_alloc(probe_values(seed), 16)
    m = 16
    while m < sch.TIE_RUNG:
        assert len(sch.variants(alloc, 16, m)) == 1
        m *= 2


def test_candidates_start_with_the_exact_allocation():
    vals = np.array([0.0, 0.1, 0.35, 0.36, 1.0])
    cands = sch.candidate_allocs(vals, 16, slack=0.0)
    assert len(cands) == 1 and np.array_equal(cands[0], sch.paper_alloc(vals, 16))
    wide = sch.candidate_allocs(vals, 16, slack=0.05)
    assert np.array_equal(wide[0], cands[0]) and 1 < len(wide) <= 8
    assert all(c.sum() == 16 and c.min() >= 1 for c in wide)
