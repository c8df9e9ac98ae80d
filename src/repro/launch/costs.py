"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):
  compute term    = HLO_FLOPs / peak_FLOPs            (197 TF/s bf16/chip)
  memory term     = HLO_bytes / HBM_bw                (819 GB/s/chip)
  collective term = collective_bytes / link_bw        (~50 GB/s/link ICI)

`cost_analysis()` on an SPMD-partitioned module is already per-device.
Collective bytes are NOT in cost_analysis: we parse the compiled HLO and
sum operand bytes of all-gather / all-reduce / reduce-scatter / all-to-all
/ collective-permute ops (all-reduce counts 2x: reduce-scatter +
all-gather phases of a ring).

Known caveat handled here: XLA counts `while`-loop bodies ONCE. The
dry-run therefore unrolls layer stacks (exact); the one remaining
sequential scan (sLSTM over time) gets an analytic body x trip-count
correction reported separately.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# TPU v5e-class hardware constants (assignment-specified)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link (per-direction)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


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


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind from compiled HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result = <shape> <op>(...)  e.g. %ar = f32[8,128]{1,0} all-reduce(
        # (shapes may carry {layout} suffixes; tuples may nest them)
        m = re.match(r"^%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],]+(?:\{[\d,]*\})?)"
                     r"\s+([a-z\-]+)", s)
        if not m:
            continue
        op = m.group(2)
        if op.rstrip("-start").rstrip("-done") in _COLLECTIVES:
            op = op.replace("-start", "").replace("-done", "")
        if op not in _COLLECTIVES:
            continue
        if "-done" in s.split("=")[1][:64]:
            continue
        nbytes = _shape_bytes(m.group(1))
        mult = 2 if op == "all-reduce" else 1   # ring RS + AG phases
        out[op] += nbytes * mult
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                  # per-device
    bytes_accessed: float         # per-device HBM traffic
    coll_bytes: float             # per-device collective payload
    coll_breakdown: Dict[str, int]
    flops_correction: float = 0.0  # analytic scan-body corrections

    @property
    def t_compute(self) -> float:
        return (self.flops + self.flops_correction) / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "flops_correction": self.flops_correction,
            "bytes_accessed": self.bytes_accessed,
            "coll_bytes": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }


def extract(compiled, flops_correction: float = 0.0) -> RooflineTerms:
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return RooflineTerms(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        flops_correction=flops_correction,
    )


def memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        # donated inputs alias outputs, so live = max(args, outputs) + temps
        "peak_bytes_est": int(max(ma.argument_size_in_bytes,
                                  ma.output_size_in_bytes)
                              + ma.temp_size_in_bytes),
    }


def model_flops(cfg, shape, n_chips: int) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) per *device* per step.

    Train counts fwd+bwd (6ND); prefill counts forward only (2ND);
    decode counts one token (2*N_active per sequence)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens / n_chips
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens / n_chips
    return 2.0 * n_active * shape.global_batch / n_chips


def slstm_correction(cfg, shape, n_chips: int) -> float:
    """Analytic FLOPs of sequential sLSTM scan bodies x trip count."""
    from ..models.xlstm import slstm_analytic_flops
    n_slstm = sum(1 for k in cfg.layer_kinds() if k == "slstm")
    if n_slstm == 0:
        return 0.0
    if shape.kind == "decode":
        seq = 1
    else:
        seq = shape.seq_len
    per_layer = slstm_analytic_flops(shape.global_batch, seq, cfg.d_model,
                                     cfg.num_heads)
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd+bwd
    return mult * n_slstm * per_layer / n_chips
