"""The command exits without a result where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

from bench.harness import spec

CELL = "vit-s16.occlusion.poisson"


def run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_non_zero_without_a_result():
    p = run(spec.ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert no_result(p.stdout)
