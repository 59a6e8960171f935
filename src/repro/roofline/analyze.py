"""Three-term roofline from a compiled dry-run artifact (deliverable g).

    compute    = FLOPs_per_chip       / peak_FLOP/s
    memory     = HBM_bytes_per_chip   / HBM_bw
    collective = coll_bytes_per_chip  / link_bw

Sources: ``compiled.cost_analysis()`` (flops, bytes accessed) — on an SPMD
partitioned module these are PER-PARTITION numbers (one partition == one
chip), verified empirically in tests/test_roofline.py by comparing 1- vs
N-device lowers. collective bytes come from parsing the post-SPMD HLO
(``compiled.as_text()``): we sum *operand* bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute instruction.

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (one link per mesh hop; we charge each collective its
operand bytes over one link, the standard bandwidth-optimal-ring estimate).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig


@dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float  # per chip, bf16
    hbm_bw: float  # bytes/s per chip
    link_bw: float  # bytes/s per ICI link
    hbm_bytes: float  # capacity per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM per chip. link_bw is the per-link ICI share this module charges
# a collective (1,600 Gbit/s of chip-to-chip interconnect over 4 links).
HW_V5E = Hardware(
    name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9, hbm_bytes=16e9
)

# NOT a device model: the ranking model the autotuner (repro.serve.autotune)
# uses when the CPU test backend runs it. Round numbers — only the ratio of
# its roofline terms orders candidate configs; no CPU figure is a device
# metric.
HW_CPU_HOST = Hardware(
    name="cpu_host", peak_flops=2e12, hbm_bw=100e9, link_bw=25e9,
    hbm_bytes=64e9,
)

# exact jax ``device_kind`` -> hardware model
HW_BY_KIND: dict[str, Hardware] = {
    "TPU v5 lite": HW_V5E,
    "cpu": HW_CPU_HOST,
}


def hardware_for(device_kind: str) -> Hardware:
    """Resolve a ``jax.Device.device_kind`` string to a hardware model.

    A kind with no table row is an error, never a guess.

        >>> hardware_for("cpu").name
        'cpu_host'
        >>> hardware_for("TPU v5 lite").name
        'tpu_v5e'
    """
    try:
        return HW_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware model for device_kind {device_kind!r}; "
            f"known: {sorted(HW_BY_KIND)}"
        ) from None


def hotpath_terms(cost: dict, hw: Hardware) -> dict:
    """Roofline terms for one stage-2 executable's ``cost_analysis`` dict.

    Returns ``{bytes_accessed, flops, memory_s, compute_s, bound_s,
    dominant}`` — the per-bucket budget the serving-path autotuner ranks
    candidate (chunk, block) configs with (DESIGN.md §10): ``bound_s`` is
    the roofline step-time estimate max(memory_s, compute_s), ``dominant``
    names the binding term.
    """
    nbytes = float(cost.get("bytes accessed", 0.0))
    flops = float(cost.get("flops", 0.0))
    memory_s = nbytes / hw.hbm_bw
    compute_s = flops / hw.peak_flops
    return {
        "bytes_accessed": nbytes,
        "flops": flops,
        "memory_s": memory_s,
        "compute_s": compute_s,
        "bound_s": max(memory_s, compute_s),
        "dominant": "memory" if memory_s >= compute_s else "compute",
    }

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

# '%name = bf16[128,4096]{1,0} op-name(%a, %b), ...'
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>\([^)]*\)|[\w\[\],{}/ ]+?)\s+"
    r"(?P<op>[\w\-]+)\((?P<operands>[^)]*)\)"
)
_SHAPE_RE = re.compile(r"(?P<dt>\w+)\[(?P<dims>[\d,]*)\]")

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def cost_analysis_dict(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()`` across jax versions.

    Older jax returns ``[dict]``, newer returns ``dict``; either may be
    empty. Always returns a plain dict.
    """
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c) if c else {}


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (sums tuple elements)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes per collective kind from (post-SPMD) HLO text.

    Returns {kind: bytes, ..., 'total': bytes}. ``-start`` variants (async
    collectives) are counted; their ``-done`` halves are not (zero operands
    moved twice).
    """
    shapes: dict[str, str] = {}
    pending: list[tuple[str, str]] = []  # (kind, operand names str)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        shapes[m.group("name")] = m.group("type")
        op = m.group("op")
        kind = next(
            (c for c in COLLECTIVE_OPS if op == c or op == c + "-start"), None
        )
        if kind is not None:
            pending.append((kind, m.group("operands")))

    out = {c: 0 for c in COLLECTIVE_OPS}
    opname = re.compile(r"%?([\w.\-]+)")
    for kind, operands in pending:
        for tok in operands.split(","):
            tok = tok.strip()
            mm = _SHAPE_RE.search(tok)
            if mm:  # operand written with inline type
                out[kind] += _shape_bytes(tok)
                continue
            nm = opname.match(tok)
            if nm and nm.group(1) in shapes:
                out[kind] += _shape_bytes(shapes[nm.group(1)])
    out["total"] = sum(out[c] for c in COLLECTIVE_OPS)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    hbm_bytes_per_chip: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    peak_bytes_per_chip: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate = max of the three overlapped terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips) — remat/redundancy waste catcher."""
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else float("nan")

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU at the roofline: useful flops / (chips*peak*step_time)."""
        denom = self.chips * HW_V5E.peak_flops * self.step_time_s
        return self.model_flops / denom if denom else float("nan")

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "peak_bytes_per_chip": self.peak_bytes_per_chip,
        }


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6·N_active·D for training, 2·N_active·D for inference steps.

    D = tokens processed by one step: train/prefill = B*S; decode = B*1.
    """
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per example


def roofline_report(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cost: dict,
    coll_bytes_per_chip: float,
    mflops: float,
    hw: Hardware = HW_V5E,
    peak_bytes_per_chip: float = 0.0,
) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        flops_per_chip=flops,
        hbm_bytes_per_chip=nbytes,
        coll_bytes_per_chip=coll_bytes_per_chip,
        compute_s=flops / hw.peak_flops,
        memory_s=nbytes / hw.hbm_bw,
        collective_s=coll_bytes_per_chip / hw.link_bw,
        model_flops=mflops,
        peak_bytes_per_chip=peak_bytes_per_chip,
    )
