"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × shape × mesh), in seconds, against the published
peaks of the target chip (``repro.launch.mesh.chip_peaks(device_kind)``):
  compute    = HLO_FLOPs_per_device / flops_bf16
  memory     = HLO_bytes_per_device / hbm_bw
  collective = collective_bytes_per_device / ici_bw

FLOPs/bytes/collective-bytes come from the while-aware HLO cost model
(repro.launch.hlo_cost) over ``compiled.as_text()`` — the partitioned,
per-device module. We do NOT use ``compiled.cost_analysis()`` because it
counts ``while`` bodies once, ignoring trip counts, which breaks every
scan-over-layers model (see hlo_cost docstring; the two agree on loop-free
modules). Collective bytes are result-shape bytes per op — within the ring
factor 2(n−1)/n ≈ 2 of true link traffic; the convention is constant across
configs so comparisons are valid.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# one HLO op line: "%name = f32[12,34]{1,0} all-reduce(...)" or tuple results
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([a-z0-9-]+)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, dict]:
    """Sum result bytes and count per collective kind."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        # strip fusion/custom-call suffixes: match exact collective names
        base = op.rstrip(".0123456789")
        if base.endswith("-start"):
            base = base[:-6]
        if base in out:
            out[base]["bytes"] += _shape_bytes(shape_str)
            out[base]["count"] += 1
    return out


@dataclass
class Roofline:
    flops: float                 # per-device HLO FLOPs
    bytes_accessed: float        # per-device HLO bytes
    collective_bytes: float      # per-device collective result bytes
    device_kind: str             # target chip, a key of mesh.CHIP_PEAKS
    collectives: dict = field(default_factory=dict)

    def __post_init__(self):
        self.peaks = chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks["flops_bf16"]

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / self.peaks["hbm_bw"]

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks["ici_bw"]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "device_kind": self.device_kind,
            "flops_per_dev": self.flops,
            "bytes_per_dev": self.bytes_accessed,
            "collective_bytes_per_dev": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "collectives": self.collectives,
        }


def roofline_from_compiled(compiled, device_kind: str,
                           lowered_text: str | None = None) -> Roofline:
    text = lowered_text if lowered_text is not None else compiled.as_text()
    cost = analyze_hlo(text)
    return Roofline(flops=cost.flops, bytes_accessed=cost.bytes_accessed,
                    collective_bytes=cost.collective_bytes,
                    device_kind=device_kind, collectives=cost.collectives)


def model_flops_per_token(n_active_params: int) -> float:
    """MODEL_FLOPS = 6·N per token (fwd+bwd); 2·N for inference fwd."""
    return 6.0 * n_active_params
