"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).

Meshes:
  single pod:  (data=16, model=16)                 — 256 chips (one v5e pod)
  multi-pod:   (pod=2, data=16, model=16)          — 512 chips

Axis semantics across the stack:
  pod    — outermost data parallelism; gradient all-reduce crosses DCN here.
  data   — in-pod data parallelism (+ FSDP shard axis, + sequence-parallel
           axis for long-context decode).
  model  — tensor parallelism: heads / mlp / vocab / experts (EP) / SSM heads.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto.

    ``make_mesh`` defaults to Explicit axes, under which
    ``with_sharding_constraint`` (``sharding.context``) and the pjit-style
    ``in_shardings`` of the explain executables are refused; the whole stack
    is written for compiler-propagated (Auto) shardings.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many real devices exist (tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh_arg(spec: str) -> tuple[int, int]:
    """``--mesh dp,tp`` -> (dp, tp). A bare ``dp`` means tp=1."""
    parts = [int(p) for p in spec.split(",") if p.strip()]
    if not 1 <= len(parts) <= 2 or any(p < 1 for p in parts):
        raise ValueError(f"--mesh wants 'dp' or 'dp,tp' with positive ints, got {spec!r}")
    return (parts[0], parts[1] if len(parts) == 2 else 1)


def ensure_host_devices(n: int) -> None:
    """Request ``n`` virtual CPU devices for multi-device demos on one host.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``,
    which only takes effect if the JAX backend has not initialized yet — call
    this before the first array op / ``jax.devices()``. Raises with the
    manual-override instruction if the backend beat us to it (DESIGN.md §9;
    docs/sharding.md shows the end-to-end demo).
    """
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )
    if jax.device_count() < n:  # initializes the backend — the final word
        raise RuntimeError(
            f"need {n} devices but the JAX backend already initialized with "
            f"{jax.device_count()}; relaunch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}"
        )


def make_explain_mesh(dp: int, tp: int = 1):
    """(data=dp, model=tp) mesh for mesh-sharded explanation serving.

    ``data`` carries the folded (batch × step) stage-2 axis
    (``repro.sharding.explain_specs``); ``model`` is plumbed for backbone
    tensor parallelism and may be 1.
    """
    return _auto_mesh((dp, tp), ("data", "model"))
