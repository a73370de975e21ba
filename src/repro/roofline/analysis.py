"""Roofline analysis from the compiled dry-run artifact (no hardware).

Three terms per (arch x shape x mesh) cell, in seconds:

  compute    = HLO_FLOPs_total   / (chips x peak_FLOP/s)
  memory     = HLO_bytes_total   / (chips x HBM_bw)
  collective = collective_bytes  / (chips x link_bw)

Sources: ``compiled.cost_analysis()`` reports *per-device* flops and bytes
(verified against hand-counted shards), so the chips factors cancel:
term = per_device_quantity / per_chip_rate. collective_bytes comes from
parsing the post-SPMD HLO (``compiled.as_text()``): we sum the result-shape
bytes of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute instruction (async -start counted once, -done skipped).

Two collective accountings are kept:
  raw   — sum of result-shape bytes (the assignment's convention)
  wire  — ring-model bytes actually crossing links per device:
          all-reduce 2(n-1)/n x bytes, all-gather/reduce-scatter/all-to-all
          (n-1)/n x full bytes, permute 1x. Used for hillclimb deltas.

Hardware constants (TPU v5e class, from the assignment):
  197 TFLOP/s bf16 per chip | 819 GB/s HBM | ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig


@dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    link_bw: float = 50e9               # bytes/s per ICI link

V5E = HW()

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# '%name = <shapes> <op>(' — op must be the instruction, not an operand ref
_INSTR_RE = re.compile(
    r"=\s+(?P<shapes>[^=]*?)\s+(?P<op>" + "|".join(_COLL_OPS)
    + r")(?P<start>-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            n = int(np.prod([int(d) for d in dims.split(",") if d]))
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    raw_bytes: int = 0                  # sum of result-shape bytes
    wire_bytes: float = 0.0             # ring-model per-device link bytes
    count: int = 0
    by_op: Dict[str, int] = field(default_factory=dict)
    by_op_count: Dict[str, int] = field(default_factory=dict)
    largest: List[Tuple[int, str]] = field(default_factory=list)

    def add(self, op: str, nbytes: int, group_size: int, line: str):
        self.raw_bytes += nbytes
        self.count += 1
        self.by_op[op] = self.by_op.get(op, 0) + nbytes
        self.by_op_count[op] = self.by_op_count.get(op, 0) + 1
        n = max(group_size, 2)
        if op == "all-reduce":
            wire = 2.0 * (n - 1) / n * nbytes
        elif op in ("all-gather", "all-to-all"):
            wire = (n - 1) / n * nbytes
        elif op == "reduce-scatter":
            wire = (n - 1) * nbytes      # result is the scattered shard
        else:                            # collective-permute
            wire = float(nbytes)
        self.wire_bytes += wire
        self.largest.append((nbytes, line.strip()[:160]))
        self.largest.sort(reverse=True)
        del self.largest[8:]


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m or m.group("start") == "-done":
            continue
        nbytes = _shape_bytes(m.group("shapes"))
        g = _GROUPS_RE.search(line)
        group_size = int(g.group(2)) if g else 2
        stats.add(m.group("op"), nbytes, group_size, line)
    return stats


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6*N*D)
# ---------------------------------------------------------------------------
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D (dense) or 6*N_active*D (MoE); D = tokens processed by the
    lowered program (decode cells process global_batch x 1 token).
    Whisper counts encoder+decoder tokens. Training = fwd+bwd (the full 6);
    inference-only cells use 2*N*D (fwd only)."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        d_tokens = shape.global_batch * shape.seq_len
        if cfg.is_encoder_decoder:
            d_tokens *= 2   # encoder frames + decoder tokens (both seq_len)
        return 6.0 * n_active * d_tokens
    if shape.is_decode:
        return 2.0 * n_active * shape.global_batch
    d_tokens = shape.global_batch * shape.seq_len
    if cfg.is_encoder_decoder:
        d_tokens *= 2
    return 2.0 * n_active * d_tokens


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------
@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_raw_bytes: int
    collective_wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    collective_wire_s: float
    bottleneck: str
    model_flops_total: float
    useful_flop_ratio: float            # MODEL_FLOPS / (HLO_FLOPs x chips)
    arg_bytes: int = 0
    temp_bytes: int = 0
    out_bytes: int = 0
    coll_by_op: Dict[str, int] = field(default_factory=dict)
    coll_count: int = 0
    largest_collectives: List[Tuple[int, str]] = field(default_factory=list)

    @property
    def step_s(self) -> float:
        """Roofline step time if the three terms overlap perfectly:
        max(terms) — the optimistic bound the perf loop drives down."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound step time, i.e. how close the cell
        is to pure-MFU execution at the bound."""
        chips = max(self.chips, 1)
        useful_s = self.model_flops_total / (chips * V5E.peak_flops)
        return useful_s / self.step_s if self.step_s > 0 else 0.0

    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d["step_s"] = self.step_s
        d["roofline_fraction"] = self.roofline_fraction
        return d


def roofline_terms(flops_dev: float, bytes_dev: float,
                   coll: CollectiveStats, *, chips: int,
                   hw: HW = V5E) -> Tuple[float, float, float, float]:
    compute_s = flops_dev / hw.peak_flops
    memory_s = bytes_dev / hw.hbm_bw
    # assignment convention: collective_bytes / (chips x link_bw) with the
    # parsed totals being per-device already -> divide by link_bw
    collective_s = coll.raw_bytes / hw.link_bw
    collective_wire_s = coll.wire_bytes / hw.link_bw
    return compute_s, memory_s, collective_s, collective_wire_s


def analyze_compiled(compiled, *, arch: str, shape_cfg: ShapeConfig,
                     cfg: ModelConfig, mesh_name: str, chips: int,
                     hw: HW = V5E,
                     hlo_text: Optional[str] = None) -> RooflineReport:
    """Primary numbers come from the trip-count-aware HLO walk
    (roofline/hlo_cost.py); XLA's flat cost_analysis (which counts while
    bodies once) is recorded as a cross-check."""
    from repro.roofline import hlo_cost

    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    totals = hlo_cost.analyze_hlo_text(text)
    flops_dev = max(totals.flops, xla_flops)
    bytes_dev = max(totals.bytes, xla_bytes)

    compute_s = flops_dev / hw.peak_flops
    memory_s = bytes_dev / hw.hbm_bw
    collective_s = totals.coll_raw / hw.link_bw
    wire_s = totals.coll_wire / hw.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape_cfg)
    ratio = mf / (flops_dev * chips) if flops_dev else 0.0
    try:
        ma = compiled.memory_analysis()
        arg_b, temp_b, out_b = (ma.argument_size_in_bytes,
                                ma.temp_size_in_bytes,
                                ma.output_size_in_bytes)
    except Exception:
        arg_b = temp_b = out_b = 0
    rep = RooflineReport(
        arch=arch, shape=shape_cfg.name, mesh=mesh_name, chips=chips,
        flops_per_device=flops_dev, bytes_per_device=bytes_dev,
        collective_raw_bytes=int(totals.coll_raw),
        collective_wire_bytes=totals.coll_wire,
        compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, collective_wire_s=wire_s,
        bottleneck=bottleneck, model_flops_total=mf,
        useful_flop_ratio=ratio,
        arg_bytes=arg_b, temp_bytes=temp_b, out_bytes=out_b,
        coll_by_op={k: int(v) for k, v in totals.coll_by_op.items()},
        coll_count=int(totals.coll_count),
        largest_collectives=[(int(b), d)
                             for b, d in totals.largest_collectives],
    )
    rep.xla_flops = xla_flops       # cross-checks (flat, while-body-once)
    rep.xla_bytes = xla_bytes
    rep.while_trips = dict(totals.while_trips)
    return rep
