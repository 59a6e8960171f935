"""Mesh-sharded serving at the LM's served bf16 compute (DESIGN.md §9).

``test_mesh_explain.py`` holds the sharded engine to the one-device engine
at f32 compute in a process with 4 forced host devices. This file holds the
same claim at the bf16 compute a reduced LM is served at, in a child process
of its own, so it runs in every test process.

On XLA:CPU the claim needs single-threaded Eigen contractions
(``--xla_cpu_multi_thread_eigen=false``): the threaded contraction splits
its sums by output shape, and each device of a data=4 mesh runs a quarter
of the bucket's rows, so a bf16 forward rounds differently on the mesh than
on one device: f(x) moves by a bf16 rounding of a logit
(about 3.5e-4), δ with it, and so can the adaptive ladder's exit rung. With
single-threaded contractions the batch extent does not change a row's sums,
and endpoints, δ, traces and scores are bit-identical.
"""

_SCRIPT = r"""
import numpy as np
from repro.configs import ARCHS, reduced
from repro.launch.mesh import make_explain_mesh
from repro.models.registry import Model
import test_mesh_explain as t

cfg = reduced(ARCHS["llama3-8b"])
assert cfg.compute_dtype == "bfloat16", cfg.compute_dtype
params = Model(cfg).init(t.KEY)
mesh = make_explain_mesh(4, 1)
reqs = t._requests(cfg, t.MIXED_LENS, seed=1)
for kw in (
    dict(method="expected_grad", n_samples=2),
    dict(method="ig", m=4, adaptive=True, tol=1e-2, m_max=16),
):
    single, sharded = t._pair(cfg, params, mesh, **kw)
    for a, b in zip(single.explain(reqs), sharded.explain(reqs)):
        trace = ("m_used", "hops", "converged")
        assert [a.get(k) for k in trace] == [b.get(k) for k in trace], (kw, a, b)
        np.testing.assert_array_equal(a["token_scores"], b["token_scores"])
        for k in ("f_x", "f_baseline", "delta"):
            assert a[k] == b[k], (kw, k, a[k], b[k])
    assert sharded.stats.mesh_fallbacks == 0
    assert all(sh is not None for _, sh in sharded._cache.values())
print("ok")
"""


def test_bf16_sharded_bit_identical_to_one_device(run_child):
    out = run_child(
        _SCRIPT,
        "--xla_force_host_platform_device_count=4 --xla_cpu_multi_thread_eigen=false",
    )
    assert out.strip().endswith("ok")
