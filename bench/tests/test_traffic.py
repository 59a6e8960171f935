"""The traffic generator is a function of the mix file and the seed."""
import jax
import numpy as np
import pytest

from bench.harness import spec, traffic as tr

BM = spec.load_benchmark()
BIG_SEED = 2**31 + 977


def test_open_loop_arrivals_are_the_same_for_every_seed():
    mix = spec.traffic_file("occlusion.poisson")
    a = tr.arrivals(mix, 40.0)
    assert np.array_equal(a, tr.arrivals(mix, 40.0))
    gaps = np.diff(np.concatenate([[0.0], a]))
    # exponential quantiles at the mix's rate, in a shuffled order
    rate = mix["rate_per_s"]
    assert abs(np.mean(gaps) * rate - 1.0) < 0.05
    assert not np.all(np.diff(gaps) >= 0)
    assert a[-1] > 40.0  # the pool outlasts the window


def test_closed_loop_pool_covers_the_window():
    mix = spec.traffic_file("ig-uniform-m16.backlog")
    n = tr.pool_size(mix, 30.0)
    assert n >= mix["outstanding"] + 30 * mix["max_rate_per_s"]


def test_seed_beyond_32_bits():
    rng, key = tr.rngs(2**32 + 5)
    assert 0 <= key < 2**31
    assert jax.random.PRNGKey(key).shape == (2,)
    rng.integers(0, 10)


@pytest.mark.parametrize("seed", [BIG_SEED, 12])
def test_prompts_are_seeded_with_one_set_of_lengths(seed):
    info = spec.cell(BM, "mamba2-780m.ig-uniform-m16.backlog")
    mix, sizes, model = info["traffic"], info["sizes"], info["model"]

    def make(s):
        return model.make_inputs(sizes, None, mix, None, tr.rngs(s)[0], 40)

    a, b, c = make(seed), make(seed), make(seed + 1)
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"]) and x["target"] == y["target"]
    lens = lambda reqs: sorted(len(r["tokens"]) for r in reqs)
    assert lens(a) == lens(c)
    assert [len(r["tokens"]) for r in a] != [len(r["tokens"]) for r in c]
    assert min(lens(a)) >= mix["min_len"] and max(lens(a)) <= mix["max_len"]


def test_images_are_seeded():
    info = spec.cell(BM, "vit-s16.occlusion.poisson")
    sizes = dict(info["sizes"], image_size=32, patch_size=8, num_layers=1, d_model=32,
                 num_heads=2, d_ff=64, num_classes=10)
    model = info["model"]
    params = model.init_params(sizes, jax.random.PRNGKey(3))

    def make(s):
        rng, key = tr.rngs(s)
        return model.make_inputs(sizes, params, info["traffic"], jax.random.PRNGKey(key), rng, 5)

    a, b, c = make(BIG_SEED), make(BIG_SEED), make(BIG_SEED + 1)
    assert all(np.array_equal(x["features"], y["features"]) for x, y in zip(a, b))
    assert [x["target"] for x in a] == [y["target"] for y in b]
    assert not np.array_equal(a[0]["features"], c[0]["features"])
    assert a[0]["features"].shape == (16, 8 * 8 * 3)
