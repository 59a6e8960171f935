"""Shared fixtures. NOTE: never set xla_force_host_platform_device_count
here — smoke tests and benches must see the 1 real CPU device; only
``repro.launch.dryrun`` (its own process) requests 512 placeholders.
"""
import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def rng():
    """Per-test-seeded numpy Generator: every RNG-dependent test draws from
    its own fixed stream, so failures reproduce regardless of which other
    tests ran (no shared global numpy state)."""
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def run_child():
    """Run a Python snippet in a fresh process with extra ``XLA_FLAGS``
    (flags that must be set before backend init, e.g. single-threaded Eigen
    contractions or forced host devices); ``src/`` and ``tests/`` are
    importable there. Returns the snippet's stdout; a non-zero exit fails
    the calling test with the tail of its stderr."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(script: str, xla_flags: str, timeout: int = 900) -> str:
        env = dict(
            os.environ,
            PYTHONPATH=os.pathsep.join(
                [os.path.join(root, "src"), os.path.join(root, "tests")]
            ),
            XLA_FLAGS=xla_flags,
            JAX_PLATFORMS="cpu",
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env=env, cwd=root, timeout=timeout,
        )
        assert proc.returncode == 0, proc.stderr[-4000:]
        return proc.stdout

    return run
