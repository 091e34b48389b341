"""Roofline-term derivation from the compiled dry-run artifact.

These terms come from the compiled program, not from a run: the
dry-run compiles for placeholder devices without executing anything,
so the three roofline terms are derived structurally:

    compute    = HLO_FLOPs_per_device  / PEAK_FLOPS
    memory     = HLO_bytes_per_device  / HBM_BW
    collective = coll_bytes_per_device / ICI_BW

`compiled.cost_analysis()` analyzes the post-SPMD per-device module, so
its 'flops' / 'bytes accessed' are already per-chip; dividing by
per-chip peaks gives seconds directly (equivalent to the assignment's
global-bytes / (chips x bw) form).

Collective bytes are NOT in cost_analysis: we parse the optimized HLO
(`compiled.as_text()`) and sum OPERAND sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute /
ragged-all-to-all op (operand size = the per-device payload handed to
the fabric; the convention the assignment specifies).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all", "all-gather-start",
    "all-reduce-start", "collective-permute-start",
)

# `  %x = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %p), ...`
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][a-z0-9-]*)\((.*)$")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = _DTYPE_BYTES[dtype]
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _operand_bytes(operands: str) -> int:
    """Sum dtype[shape] operand sizes up to the closing paren."""
    depth, end = 1, len(operands)
    for i, ch in enumerate(operands):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return sum(_shape_bytes(d, s) for d, s in
               _SHAPE_RE.findall(operands[:end]))


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device payload bytes of every collective in an HLO module,
    grouped by op kind."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op, operands = m.group(1), m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if base not in COLLECTIVE_OPS:
            continue
        out[base] = out.get(base, 0) + _operand_bytes(operands)
    return out


def count_collectives(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m:
            op = m.group(1)
            base = op[:-6] if op.endswith("-start") else op
            if base in COLLECTIVE_OPS:
                out[base] = out.get(base, 0) + 1
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    hlo_flops: float             # per device
    hlo_bytes: float             # per device
    coll_bytes: float            # per device
    coll_by_kind: Dict[str, int]
    model_flops_total: float     # analytic useful FLOPs (whole step)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    useful_ratio: float = 0.0    # MODEL_FLOPS / (HLO_FLOPs * chips)
    roofline_fraction: float = 0.0
    mem_per_device: Optional[float] = None

    def finish(self) -> "RooflineReport":
        self.t_compute = self.hlo_flops / PEAK_FLOPS
        self.t_memory = self.hlo_bytes / HBM_BW
        self.t_collective = self.coll_bytes / ICI_BW
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.bottleneck = max(terms, key=terms.get)
        total_hlo = self.hlo_flops * self.n_chips
        self.useful_ratio = (self.model_flops_total / total_hlo
                             if total_hlo else 0.0)
        # roofline fraction: useful FLOPs at peak vs. the step's dominant
        # term — "how close does the step run to the best achievable".
        t_ideal = self.model_flops_total / (self.n_chips * PEAK_FLOPS)
        t_step = max(terms.values())
        self.roofline_fraction = (t_ideal / t_step) if t_step else 0.0
        return self

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape_cell) -> float:
    """Analytic useful FLOPs for the step: 6·N·D train (fwd+bwd),
    2·N·D forward-only (prefill/decode); N = active params (MoE)."""
    n = cfg.active_param_count()
    tokens = shape_cell.global_batch * (
        shape_cell.seq_len if shape_cell.kind in ("train", "prefill") else 1)
    mult = 6.0 if shape_cell.kind == "train" else 2.0
    return mult * n * tokens


def analyze(compiled, *, arch: str, shape: str, mesh_name: str,
            n_chips: int, model_flops_total: float) -> RooflineReport:
    """Primary source: the HLO call-graph walker (hlo_costs) — XLA's own
    cost_analysis counts while bodies once, which under-reports a
    scan-over-layers step by ~n_layers x microbatches."""
    from . import hlo_costs
    hlo = compiled.as_text()
    cost = hlo_costs.module_costs(hlo)
    flops = float(cost.flops)
    byts = float(cost.hbm_bytes)
    coll = {k: int(v) for k, v in cost.coll.items()}
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                    ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, n_chips=n_chips,
        hlo_flops=flops, hlo_bytes=byts,
        coll_bytes=float(sum(coll.values())), coll_by_kind=coll,
        model_flops_total=model_flops_total, mem_per_device=mem,
    ).finish()
