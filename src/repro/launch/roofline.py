"""Roofline analysis of compiled dry-run cells (TPU v5e targets).

Per (arch, cell, mesh):

    compute    = device_flops            / peak_flops        [s]
    memory     = device_hbm_bytes        / hbm_bw            [s]
    collective = device_link_bytes       / link_bw           [s]

with the per-device, while-loop-adjusted numbers from launch/hlo_analysis.py
(``compiled.cost_analysis()`` counts loop bodies once — verified — so the
loop-adjusted reparse is the honest source; the raw cost_analysis numbers
are recorded alongside for reference).

Hardware constants (per chip): 197 TFLOP/s bf16 (x2 for int8 paths), 819
GB/s HBM, ~50 GB/s/link ICI.  The dominant term is the bottleneck; its
ratio to the wall-clock lower bound (max of terms) is what §Perf iterates
down.  MODEL_FLOPS = 6 * N_active * D; the MODEL_FLOPS / HLO_FLOPS ratio
flags remat/redundancy waste.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

import numpy as np

from repro.launch import hlo_analysis

__all__ = ["HW", "RooflineReport", "roofline", "format_row"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12        # bf16 / chip
    peak_flops_int8: float = 394e12
    hbm_bw: float = 819e9             # bytes/s
    link_bw: float = 50e9             # bytes/s/link


@dataclasses.dataclass
class RooflineReport:
    arch: str
    cell: str
    mesh: str
    chips: int
    # per-device loop-adjusted costs
    device_flops: float
    device_bytes: float
    device_link_bytes: float
    per_collective: Dict[str, float]
    # terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float               # MODEL_FLOPS / (chips * device_flops)
    # raw cost_analysis (loop bodies counted once) for reference
    raw_flops: Optional[float] = None
    raw_bytes: Optional[float] = None
    memory_per_device: Optional[dict] = None
    int8: bool = False                # compute term used the int8 peak

    @property
    def step_time_lb(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the per-chip compute roofline achieved at the
        step-time lower bound (the §Perf score)."""
        if self.step_time_lb == 0:
            return 0.0
        return self.t_compute / self.step_time_lb

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["step_time_lb"] = self.step_time_lb
        d["roofline_fraction"] = self.roofline_fraction
        return d


def roofline(arch: str, cell: str, mesh_name: str, chips: int,
             compiled, model_flops: float, hw: HW = HW(),
             int8: bool = False) -> RooflineReport:
    cost = hlo_analysis.analyze(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_d = None
    if mem is not None:
        mem_d = dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
            output_bytes=getattr(mem, "output_size_in_bytes", 0),
            temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
            alias_bytes=getattr(mem, "alias_size_in_bytes", 0),
        )
    peak = hw.peak_flops_int8 if int8 else hw.peak_flops
    t_c = cost.flops / peak
    t_m = cost.bytes / hw.hbm_bw
    t_l = cost.collective_bytes / hw.link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_l}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops / (chips * cost.flops) if cost.flops else 0.0
    return RooflineReport(
        arch=arch, cell=cell, mesh=mesh_name, chips=chips,
        device_flops=cost.flops, device_bytes=cost.bytes,
        device_link_bytes=cost.collective_bytes,
        per_collective=dict(cost.per_collective),
        t_compute=t_c, t_memory=t_m, t_collective=t_l,
        bottleneck=bottleneck, model_flops=model_flops,
        useful_ratio=useful,
        raw_flops=ca.get("flops"), raw_bytes=ca.get("bytes accessed"),
        memory_per_device=mem_d, int8=int8,
    )


def format_row(r: RooflineReport) -> str:
    return (f"{r.arch:22s} {r.cell:12s} {r.mesh:10s} "
            f"comp {r.t_compute*1e3:9.2f}ms mem {r.t_memory*1e3:9.2f}ms "
            f"coll {r.t_collective*1e3:9.2f}ms -> {r.bottleneck:10s} "
            f"useful {r.useful_ratio*100:5.1f}% "
            f"roofline_frac {r.roofline_fraction*100:5.1f}%")
