"""Each configuration cuts its own CPU stand-in (``small`` in
``bench/configs/<config>.py``), and the tests' cells are cut from it.

A stand-in keeps its configuration file's keys and adds none, keeps a whole
period of the layer pattern, and stays under a CPU budget of float32
weights: 4 MiB for the stand-in that runs the whole harness (every engine
executable is compiled for it, on up to six test workers sharing one
machine), 256 MiB for the control test's (the reference and its control
take gradients at that size, three seeds in one worker)."""
import pytest

from bench.harness import spec
from bench.tests import small

RUN_BUDGET = 4 * 2**20
CONTROL_BUDGET = 256 * 2**20
CONFIGS = [c["name"] for c in small.BM["configs"]]


def config(name):
    _, sizes, model = spec.config_files(small.BM, name)
    return sizes, model


def period(kinds: list) -> int:
    """The length of the shortest prefix that the pattern repeats."""
    return next(p for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p))


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_stand_in_keeps_the_keys_and_a_whole_layer_period(name, control):
    sizes, model = config(name)
    cut = model.small(sizes, control=control)
    assert set(cut) == set(sizes)
    assert set(cut["engine"]) == set(sizes["engine"])
    assert cut["name"] == sizes["name"] and cut["reduced"] == sizes["reduced"]
    full, got = model.layer_kinds(sizes), model.layer_kinds(cut)
    p = period(full)
    assert set(got) == set(full)
    if p < len(full):  # a periodic pattern: whole periods of it
        assert len(got) % p == 0 and got == full[:p] * (len(got) // p)
    budget = CONTROL_BUDGET if control else RUN_BUDGET
    assert model.param_bytes(cut) < budget, model.param_bytes(cut)
    assert sizes == config(name)[0]  # the configuration's own sizes are untouched


def test_period():
    assert period(["a"] * 12) == 1
    assert period(["m", "e", "m", "e", "m", "*"] * 4) == 6
    assert period(["m", "e", "m"]) == 3


def published(name, **changes):
    sizes = dict(config(name)[0])
    engine = dict(sizes["engine"], **changes.pop("engine", {}))
    return dict(sizes, engine=engine, **changes)


ENGINE = {"chunk": 4, "batch_buckets": [1, 2, 4], "max_batch": 4}
VIT = published("vit-s16", image_size=32, patch_size=4, num_classes=10, num_layers=2,
                d_model=64, num_heads=4, d_ff=128, engine=dict(ENGINE, seq_buckets=[64]))
MAMBA2 = published("mamba2-780m", num_layers=2, d_model=64, vocab_size=512, ssm_state=16,
                   ssm_head_dim=16, ssm_chunk=16, engine=dict(ENGINE, seq_buckets=[32]))
# the cuts the tests ran at before each configuration cut its own
BEFORE = {
    ("vit-s16.ig-adaptive.backlog", False): (VIT, {
        "arrivals": "closed", "outstanding": 16, "max_rate_per_s": 20.0, "method": "ig",
        "schedule": "paper", "adaptive": True, "tol": 0.01, "m": 4, "m_max": 16, "n_int": 4}),
    ("vit-s16.occlusion.poisson", False): (VIT, {
        "arrivals": "open", "rate_per_s": 6.0, "method": "occlusion", "n_masks": 16}),
    ("mamba2-780m.ig-uniform-m16.backlog", False): (MAMBA2, {
        "arrivals": "closed", "outstanding": 8, "max_rate_per_s": 6.0, "method": "ig",
        "schedule": "uniform", "adaptive": False, "m": 8, "min_len": 9, "max_len": 32}),
}
CLOSED = {"arrivals": "closed", "outstanding": 8, "max_rate_per_s": 40.0}
BEFORE.update({(cell, True): (sizes, dict(traffic, **CLOSED))
               for (cell, _), (sizes, traffic) in list(BEFORE.items())})
CONTROL_BEFORE = {
    "vit-s16.ig-adaptive.backlog": published("vit-s16", image_size=64,
                                             engine={"seq_buckets": [16]}),
    "mamba2-780m.ig-uniform-m16.backlog": MAMBA2,
}
CONTROL_BEFORE["vit-s16.occlusion.poisson"] = CONTROL_BEFORE["vit-s16.ig-adaptive.backlog"]


@pytest.mark.parametrize("cell,closed", sorted(BEFORE))
def test_cells_are_cut_as_before(cell, closed):
    sizes, traffic = BEFORE[(cell, closed)]
    assert small.cell(cell, closed=closed) == {"sizes": sizes, "traffic": traffic}


@pytest.mark.parametrize("cell", sorted(CONTROL_BEFORE))
def test_control_sizes_as_before(cell):
    want = {"sizes": CONTROL_BEFORE[cell], "traffic": BEFORE[(cell, False)][1]}
    assert small.cell(cell, control=True) == want
