"""Roofline-term extraction from a compiled (dry-run) executable.

Three terms per (arch x shape x mesh) cell, all in seconds (per step):

    compute    = HLO_FLOPs_per_device / peak_flops_per_chip
    memory     = HLO_bytes_per_device / hbm_bw_per_chip
    collective = collective_operand_bytes_per_device / (links * link_bw)

``cost_analysis()`` of a GSPMD-partitioned executable describes ONE
partition's module, so per-device terms need no further division by chip
count (equivalent to the spec formula total/(chips*peak)).

collective bytes are not in cost_analysis: we parse the post-partitioning
HLO text and sum the operand sizes of all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute ops (spec estimator; ring
factors noted in EXPERIMENTS.md).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one device kind."""

    flops: float         # bf16 FLOP/s
    hbm_bw: float        # bytes/s
    ici_bw: float        # bytes/s per link
    ici_links: int


#: Keyed by ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: of chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                              ici_links=4)}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


@dataclass
class CollectiveStats:
    op_bytes: Dict[str, int] = field(default_factory=dict)
    op_count: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.op_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand sizes of every collective op in (post-SPMD) HLO text."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in _COLLECTIVES:
            # match "  %x = bf16[..] all-reduce(" and "-start" variants
            if re.search(rf"\b{c}(-start)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        shapes = _SHAPE_RE.findall(rhs)
        if not shapes:
            continue
        # first shape(s) describe the result (possibly a tuple); operands are
        # inside the parens. Parse operands = shapes appearing after '('.
        paren = rhs.index("(")
        operand_shapes = _SHAPE_RE.findall(rhs[paren:])
        nbytes = sum(_shape_bytes(d, dims) for d, dims in operand_shapes)
        stats.op_bytes[op] = stats.op_bytes.get(op, 0) + nbytes
        stats.op_count[op] = stats.op_count.get(op, 0) + 1
    return stats


@dataclass
class RooflineTerms:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    chips: int
    device_kind: str             # keys PEAKS
    model_flops: float = 0.0     # 6*N*D (train) or 2*N_active*D (serve), global

    @property
    def peaks(self) -> Peaks:
        return peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / (
            self.peaks.ici_links * self.peaks.ici_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline lower bound: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops summed over chips)."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Roofline-implied MFU: model flops / (chips*peak*step_time)."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * self.peaks.flops * t)

    def to_dict(self) -> Dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "chips": self.chips,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
            "coll_bytes": getattr(self, "coll_bytes", {}),
            "coll_count": getattr(self, "coll_count", {}),
            "xla_flops_once": getattr(self, "xla_flops_once", 0.0),
            "xla_bytes_once": getattr(self, "xla_bytes_once", 0.0),
        }


def analyze(compiled, chips: int, model_flops: float,
            device_kind: str) -> RooflineTerms:
    """Loop-aware analysis of the compiled per-partition module, priced
    at the peaks of ``device_kind`` (see :data:`PEAKS`).

    Uses repro.roofline.hlo_parse (trip-count-aware) rather than
    ``cost_analysis()``, which counts scan bodies once (see hlo_parse docs);
    cost_analysis values are kept as cross-checks in the dry-run JSON.
    """
    from repro.roofline.hlo_parse import analyze_hlo
    peaks(device_kind)           # an unknown kind fails before parsing
    cost = analyze_hlo(compiled.as_text())
    terms = RooflineTerms(
        flops_per_device=cost.flops, bytes_per_device=cost.bytes,
        collective_bytes_per_device=cost.collective_bytes,
        chips=chips, device_kind=device_kind, model_flops=model_flops)
    terms.coll_bytes = dict(cost.coll_bytes)
    terms.coll_count = dict(cost.coll_count)
    ca = compiled.cost_analysis() or {}
    terms.xla_flops_once = float(ca.get("flops", 0.0))
    terms.xla_bytes_once = float(ca.get("bytes accessed", 0.0))
    return terms
