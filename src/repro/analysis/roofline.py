"""Three-term roofline from a compiled dry-run artifact (DESIGN.md §6).

    compute    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory     = HLO_bytes / (chips x HBM_bw)
    collective = collective_bytes / (chips x link_bw x links_used)

HLO_FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes
from the HLO text (repro.analysis.hlo_utils).  cost_analysis on the
SPMD-partitioned module reports *per-partition* numbers already; we
normalize defensively by detecting whole-module totals.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict

from repro.hw.specs import chip_spec


@dataclasses.dataclass
class RooflineReport:
    arch: str
    cell: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float              # 6*N*D (active params)
    useful_ratio: float             # model_flops / (flops_per_chip*chips)
    bottleneck: str
    step_s: float                   # max of the three terms
    hw_peak_frac: float             # compute_s / step_s (roofline fraction)
    collective_breakdown: Dict[str, float]
    bytes_accessed_detail: Dict[str, float]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _flops_from_cost(cost: dict) -> float:
    return float(cost.get("flops", 0.0))


def _bytes_from_cost(cost: dict) -> Dict[str, float]:
    detail = {k: float(v) for k, v in cost.items()
              if k.startswith("bytes accessed")}
    total = detail.get("bytes accessed", 0.0)
    return {"total": total, **detail}


def build_report(*, arch: str, cell: str, mesh_name: str, chips: int,
                 cost: dict, hlo_text: str, model_flops: float,
                 tokens_per_step: float, device_kind: str,
                 axis_group_hint: int = 16) -> RooflineReport:
    """``device_kind`` names the chip whose peaks bound the terms
    (:func:`repro.hw.specs.chip_spec` raises for an unknown one)."""
    spec = chip_spec(device_kind)
    # Trip-count-aware walker (repro.analysis.hlo_cost): XLA's own
    # cost_analysis() counts while-loop bodies once, so scan-over-layers
    # programs under-report by the trip count.  The raw cost_analysis
    # numbers are kept in the artifact for reference.
    from repro.analysis.hlo_cost import analyze as hlo_analyze
    hc = hlo_analyze(hlo_text, default_group=axis_group_hint)
    flops = hc.flops
    bdetail = _bytes_from_cost(cost)
    bdetail["xla_cost_analysis_bytes"] = bdetail.pop("total", 0.0)
    bdetail["xla_cost_analysis_flops"] = _flops_from_cost(cost)
    hlo_bytes = hc.bytes_accessed

    compute_s = flops / spec.peak_flops_bf16
    memory_s = hlo_bytes / spec.hbm_bw
    # ICI: assume the per-axis collectives use the torus links of that
    # axis; a 2D mesh gives each chip `ici_links` usable links but a
    # single collective stream typically saturates one bidirectional pair.
    coll_bw = spec.ici_link_bw * 2
    collective_s = hc.collective_bytes / coll_bw

    step_s = max(compute_s, memory_s, collective_s)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    model_flops_per_chip = model_flops / chips
    return RooflineReport(
        arch=arch, cell=cell, mesh=mesh_name, chips=chips,
        flops_per_chip=flops, bytes_per_chip=hlo_bytes,
        coll_bytes_per_chip=hc.collective_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model_flops, useful_ratio=(
            model_flops_per_chip / flops if flops else 0.0),
        bottleneck=bottleneck, step_s=step_s,
        hw_peak_frac=compute_s / step_s if step_s else 0.0,
        collective_breakdown=hc.collective_breakdown,
        bytes_accessed_detail=bdetail)


def model_flops_for(cfg, cell) -> float:
    """6*N*D for train; 2*N*D for prefill; 2*N_active*B per decode token."""
    n_active = cfg.active_params_count()
    if cell.step == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.step == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence; attention reads the cache too but
    # 2*N*B is the standard useful-FLOPs convention.
    return 2.0 * n_active * cell.global_batch


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.to_json(), f, indent=2)
