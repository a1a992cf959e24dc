"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step on the
TARGET hardware (TPU v5e):

    compute    = HLO_FLOPs(per device)      / 197e12  FLOP/s  (bf16 MXU)
    memory     = HLO_bytes(per device)      / 819e9   B/s     (HBM)
    collective = wire_bytes(per device)     / 50e9    B/s     (one ICI link)

``cost_analysis`` supplies FLOPs/bytes of the *partitioned per-device*
module.  Collective bytes are NOT in cost_analysis: we parse the optimized
HLO and sum the result-shape bytes of every collective op (all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, sync or
async-start form; `-done` twins are skipped to avoid double counting).

The dominant term is the bottleneck; MODEL_FLOPS/HLO_FLOPs measures how
much compiled compute is algorithmically useful (remat & padding waste).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Optional

PEAK_FLOPS = 197e12          # bf16 / chip (v5e)
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^=]*?\)|[a-z0-9\[\],{}:#* ]+?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind (result shapes)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        # result type precedes the op name
        prefix = line[:m.end(1) - len(kind)]
        total = sum(_shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(prefix))
        out[kind] += total
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float                 # per device
    hbm_bytes: float             # per device
    coll_bytes: float            # per device
    coll_detail: dict
    model_flops: float           # global, algorithmic
    per_device_bytes: Optional[float] = None   # peak memory (fits check)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bottleneck_cc(self) -> str:
        """Compute-vs-collective bottleneck.  The memory term from the
        CPU-backend cost_analysis is an operand-traffic UPPER BOUND (CPU
        fusion is far weaker than TPU's), so comm/compute comparisons are
        the reliable signal for schedule decisions."""
        return "compute" if self.t_compute >= self.t_collective \
            else "collective"

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the per-step time budget spent at the dominant
        hardware limit doing *useful* work: t_model_compute / t_step where
        t_step = max(terms) (perfect overlap assumption)."""
        t_step = max(self.t_compute, self.t_memory, self.t_collective)
        t_useful = (self.model_flops / self.chips) / PEAK_FLOPS
        return t_useful / t_step if t_step else 0.0

    @property
    def roofline_fraction_cc(self) -> float:
        """Useful-compute fraction against max(compute, collective) — the
        memory-term-free score used for hillclimbing (see bottleneck_cc)."""
        t_step = max(self.t_compute, self.t_collective)
        t_useful = (self.model_flops / self.chips) / PEAK_FLOPS
        return t_useful / t_step if t_step else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "collective_detail": self.coll_detail,
            "model_flops": self.model_flops,
            "per_device_peak_bytes": self.per_device_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "bottleneck_cc": self.bottleneck_cc,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "roofline_fraction_cc": self.roofline_fraction_cc,
        }


def model_flops_for(arch: str, shape_name: str) -> float:
    """Algorithmic FLOPs per step: 6·N·D train (N = active params for MoE),
    2·N·tokens for forward-only (prefill/decode)."""
    from repro import configs
    from repro.launch.shapes import SHAPES
    cfg = configs.get(arch)
    cell = SHAPES[shape_name]
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * cell.global_batch       # one token per sequence


def analyze(lowered_cell, compiled) -> Roofline:
    """Build the roofline record from a compiled dry-run cell."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    mesh_desc = lowered_cell.mesh_desc
    chips = 1
    for part in re.findall(r"(\d+)[a-z]", mesh_desc):
        chips *= int(part)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0)
                    - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(
        arch=lowered_cell.arch, shape=lowered_cell.shape, mesh=mesh_desc,
        chips=chips, flops=flops, hbm_bytes=hbm,
        coll_bytes=float(coll["total_bytes"]), coll_detail=coll,
        model_flops=model_flops_for(lowered_cell.arch, lowered_cell.shape),
        per_device_bytes=mem)
