"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), TPU v5e constants:

    compute    = HLO_FLOPs / (chips × 197e12)         [bf16 peak]
    memory     = HLO_bytes / (chips × 819e9)          [HBM BW]
    collective = collective_bytes / (chips × 50e9)    [ICI per link]

``cost_analysis()`` on the SPMD-partitioned module reports *per-device*
flops/bytes; we multiply by chip count to get the global numerators, so the
terms above reduce to per-device quantities over per-chip rates. Collective
bytes are parsed from the compiled HLO text: the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
instruction (per-device view), scaled by chips for the global numerator.

MODEL_FLOPS (6·N·tokens dense / 6·N_active·tokens MoE; 2·N for inference)
gives the useful-compute ratio — for the scan round's two-pass recompute
(FedLAMA) this correctly reports ≈0.5, surfacing the protocol-level
rematerialization cost.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

PEAK_FLOPS = 197e12       # bf16 / chip
HBM_BW = 819e9            # bytes/s / chip
ICI_BW = 50e9             # bytes/s / link

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# result like:  %all-reduce.5 = bf16[8,128,2048]{2,1,0} all-reduce(...)
# or tuples:    (f32[128]{0}, f32[64]{0}) all-gather(...)
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by each collective type (result-shape bytes)."""
    out = {op: 0.0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, _, rhs = stripped.partition("=")
        opname = None
        for op in COLLECTIVE_OPS:
            # match op at the start of the instruction (after result shape)
            if re.search(rf"\b{op}(?:-start|-done)?\(", rhs):
                opname = op
                break
        if opname is None:
            continue
        if f"{opname}-done(" in rhs:
            continue  # counted at -start
        # result shape(s) appear between '=' and the op name
        head = rhs.split(opname)[0]
        for dtype, dims in _SHAPE_RE.findall(head):
            if dtype in _DTYPE_BYTES:
                out[opname] += _shape_bytes(dtype, dims)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_per_device: float
    collective_by_type: dict
    model_flops: float            # global useful FLOPs
    memory_per_device: Optional[dict] = None
    xla_cost_raw: Optional[dict] = None   # cost_analysis() as reported
    # (undercounts while bodies; loop-aware parsed totals above are primary)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_per_device": self.collective_per_device,
            "collective_by_type": self.collective_by_type,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_ratio,
            "memory_per_device": self.memory_per_device,
            "xla_cost_raw": self.xla_cost_raw,
        }

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2)


def model_flops_for(cfg, shape_spec, flcfg=None) -> float:
    """Useful-FLOPs reference (excludes recompute/remat overheads)."""
    n_active = cfg.active_param_count()
    if shape_spec.kind == "train":
        toks = shape_spec.global_batch * shape_spec.seq * (
            flcfg.local_steps if flcfg else 1)
        return 6.0 * n_active * toks
    if shape_spec.kind == "prefill":
        return 2.0 * n_active * shape_spec.global_batch * shape_spec.seq
    # decode: one token per sequence
    return 2.0 * n_active * shape_spec.global_batch
