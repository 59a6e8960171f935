"""The control -- the reference one precision below the configuration's
(float8 weight matmuls for these bfloat16 configurations) put in the
program's place -- is refused by each cell's own limits. On the chip the
control was read at the cells' own sizes (PERF.md); here at a size a test
run can hold, on three seeds, at the size each configuration's ``small``
gives for the control (the ViT at its published widths and depth on 64 px
images, the Mamba-2 at its CPU stand-in)."""
import jax
import pytest

from bench.harness import reference as ref, runner, spec, traffic as tr
from bench.tests import small


@pytest.mark.parametrize("name", small.CELLS)
@pytest.mark.parametrize("seed", [2**31 + 101, 7, 2**32 + 3])
def test_control_is_not_correct(name, seed):
    info = spec.cell(small.BM, name)
    c = small.cell(name, control=True)
    sizes, traffic, model = c["sizes"], c["traffic"], info["model"]
    limits = runner.limits_for(name)["limits"]
    rng, key = tr.rngs(seed)
    key = jax.random.PRNGKey(key)
    params = model.init_params(sizes, jax.random.fold_in(key, 0))
    inputs = model.make_inputs(sizes, params, traffic, jax.random.fold_in(key, 1), rng, 3)
    worst: dict = {}
    for inp in inputs:
        S = runner.seq_bucket(sizes, len(inp["tokens"]))
        row = ref.Plain(model, sizes, params, inp, "f32")
        got = ref.explain(ref.Plain(model, sizes, params, inp, model.CONTROL), traffic, S)
        for k, v in ref.compare(row, traffic, S, got).items():
            worst[k] = max(worst.get(k, 0.0), v)
        # the reference's own answer reads (near) zero on every number
        own = ref.compare(row, traffic, S, ref.explain(row, traffic, S))
        assert all(own[k] <= 1e-6 * max(1.0, lim) for k, lim in limits.items()), own
    assert any(worst[k] > lim for k, lim in limits.items()), (worst, limits)
