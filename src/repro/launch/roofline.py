"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (§Roofline), against the
peaks of the chip the program targets (:data:`PEAKS`, keyed by JAX's
``device_kind``; the dry-run targets TPU v5e):

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

``compiled.cost_analysis()`` supplies per-device FLOPs/bytes (the compiled
module IS the per-device program after SPMD partitioning).  Collective bytes
are NOT in cost_analysis — we parse the partitioned HLO text and apply
ring-cost conventions per op kind:

    all-reduce        2 × tensor bytes   (reduce-scatter + all-gather phases)
    all-gather        result bytes       (each device receives ≈ the result)
    reduce-scatter    operand bytes      (each device sends ≈ the operand)
    all-to-all        tensor bytes
    collective-permute  tensor bytes

MODEL_FLOPS uses the 6·N·D (train) / 2·N·D (inference) convention with
N = active parameters; the ratio MODEL_FLOPS/HLO_FLOPs exposes remat /
redundant-compute waste.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link
    source: str


#: published per-chip peaks by ``jax.devices()[i].device_kind``
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        source='Google Cloud documentation, "TPU v5e"',
    ),
}

#: the chip the dry-run and perf drivers compile for
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind without published peaks here is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            f"repro.launch.roofline.PEAKS with their source (known: {sorted(PEAKS)})"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

# e.g. "bf16[256,1024]{1,0}" or "f32[]"; tuples handled by finditer
_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|f16|c64)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9\[\],{}()\s]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Ring-cost collective bytes per device from partitioned HLO text."""
    counts: Dict[str, int] = {}
    by_kind: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[1][:60]:
            # *-done ops re-state the shape of the matching *-start; skip
            if not m:
                continue
        kind = m.group(2)
        if f"{kind}-done" in line:
            continue
        # HLO: %name = TYPE[shape] op(TYPE[shape] %operand, ...)
        _, _, rhs = line.partition("=")
        head, _, args = rhs.partition("(")
        result_b = _shape_bytes(head)
        operand_b = _shape_bytes(args)
        if kind == "all-reduce":
            b = 2 * result_b
        elif kind == "all-gather":
            b = result_b
        elif kind == "reduce-scatter":
            b = operand_b or result_b
        else:  # all-to-all / collective-permute
            b = max(result_b, operand_b)
        counts[kind] = counts.get(kind, 0) + 1
        by_kind[kind] = by_kind.get(kind, 0) + b
    return CollectiveStats(counts, by_kind)


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    coll_counts: Dict[str, int]
    coll_by_kind: Dict[str, int]
    model_flops: float
    # memory_analysis
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0
    device_kind: str = TARGET_KIND

    @property
    def peaks(self) -> Peaks:
        return peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful compute time / bound time — the score we hillclimb."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        return (self.model_flops / self.peaks.flops) / bound if bound else 0.0

    def to_json(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "coll_bytes": self.coll_bytes,
            "coll_counts": self.coll_counts,
            "coll_by_kind": self.coll_by_kind,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "peak_bytes": self.peak_bytes,
            "device_kind": self.device_kind,
        }


def measure(compiled) -> dict:
    """Raw per-device measures from one compiled artifact."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_bytes),
        "coll_counts": dict(coll.counts),
        "coll_by_kind": dict(coll.bytes_by_kind),
    }


def extrapolate(m1: dict, m2: dict, units: float) -> dict:
    """Linear depth extrapolation: cost(U) = m1 + (U-1)·(m2-m1).

    m1/m2 come from UNROLLED 1-unit / 2-unit depth compiles (XLA's cost
    analysis counts a while-loop body once, so the scanned full-depth compile
    under-reports; unrolled small-depth compiles measure true per-layer cost
    and the stack is homogeneous by construction).
    """
    out = {}
    for key in ("flops", "bytes_accessed", "coll_bytes"):
        per = m2[key] - m1[key]
        out[key] = m1[key] + (units - 1.0) * per
    out["coll_counts"] = {
        k: int(round(m1["coll_counts"].get(k, 0) + (units - 1.0) * (m2["coll_counts"].get(k, 0) - m1["coll_counts"].get(k, 0))))
        for k in set(m1["coll_counts"]) | set(m2["coll_counts"])
    }
    out["coll_by_kind"] = {
        k: int(round(m1["coll_by_kind"].get(k, 0) + (units - 1.0) * (m2["coll_by_kind"].get(k, 0) - m1["coll_by_kind"].get(k, 0))))
        for k in set(m1["coll_by_kind"]) | set(m2["coll_by_kind"])
    }
    return out


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        ),
    }


def roofline_from(meas: dict, model_flops: float, mem: dict) -> "Roofline":
    return Roofline(
        flops=meas["flops"],
        bytes_accessed=meas["bytes_accessed"],
        coll_bytes=meas["coll_bytes"],
        coll_counts=meas["coll_counts"],
        coll_by_kind=meas["coll_by_kind"],
        model_flops=model_flops,
        **mem,
    )


def model_flops_for(cfg, shape, n_devices: int) -> float:
    """Per-device useful FLOPs per step (6ND train / 2ND inference)."""
    n_active = cfg.active_params()
    if shape.kind == "train":
        total = 6 * n_active * shape.tokens
    elif shape.kind == "prefill":
        total = 2 * n_active * shape.tokens
    else:  # decode: one token per sequence
        total = 2 * n_active * shape.global_batch
    return total / n_devices

