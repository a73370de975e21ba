"""Candidate tiling enumeration under a VMEM budget (DESIGN.md §9.1).

The paper's design space is (LMM size) x (burst length); ours is
(vmem_budget) x (block_m, block_n, block_k). A candidate is admissible iff

  * every block divides its dimension exactly (the kernels refuse partial
    tiles — ragged sizes are the mixed_exec residual's job, DESIGN.md §5);
    N is first padded up to a lane multiple (``lane_padded``), as the
    Pallas backend pads the weight rows before dispatch,
  * every block is one the TPU compiler accepts (DESIGN.md §6.3): a lane
    dimension (block_n, block_k) is a multiple of 128 or the whole
    dimension, a sublane dimension (block_m) a multiple of 8 or the whole
    dimension,
  * block_k holds whole Q8_0 blocks on the quantized paths (burst rule),
  * the kernel's ``vmem_claim_bytes`` fits the budget (the 32KB-LMM analog).

Budgets are swept from a 16KB-LMM *equivalent* up to the full per-core VMEM:
the IMAX point aggregates 46 PE-local memories per lane, so the equivalence
is ``budget_kb * AGG_UNITS`` (coverage.py's cap) mapped onto one core's
VMEM claim. ``budget_grid()`` produces that sweep.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

from repro.core.qformats import QBLOCK

# Full per-core VMEM on the v5e class (pallas_guide: ~16 MB/core); tilings
# are rejected well before this by the sweep's budgets.
VMEM_FULL_BYTES = 16 * 2**20

# TPU vreg tiling: the last (lane) axis of a block comes in 128s, the
# second-to-last (sublane) axis in 8s, unless the block spans the axis.
LANE, SUBLANE = 128, 8

# Caps on block sizes. The space is every tile-legal divisor of the
# dimension up to the cap, not just powers of two — Whisper's 1500-frame
# encoder pads to 1504 = 2^5 x 47, whose legal M tiles are 8, 16 and 32.
BLOCK_M_CAP = 256
BLOCK_N_CAP = 1024
BLOCK_K_CAP = 1024                       # burst-length analog

# Canonical power-of-two burst axis for sweep grids (benchmarks/tune_sweep).
BLOCK_K_CANDIDATES = (128, 256, 512, 1024)

KERNELS = ("q8_matmul", "q8_matvec", "bf16_matmul")


@dataclass(frozen=True)
class TileCandidate:
    """One point of the (block_m, block_n, block_k) design space."""
    kernel: str
    block_m: int
    block_n: int
    block_k: int
    vmem_bytes: int

    def as_kwargs(self) -> Dict[str, int]:
        if self.kernel == "q8_matvec":
            return {"block_n": self.block_n}
        return {"block_m": self.block_m, "block_n": self.block_n,
                "block_k": self.block_k}


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def lane_padded(n: int) -> int:
    """N after the Pallas backend pads weight rows to a lane multiple."""
    return round_up(n, LANE)


def legal_tiles(dim: int, cap: int, unit: int) -> List[int]:
    """Block sizes along one axis that tile ``dim`` exactly and that the
    TPU compiler accepts, largest first: the multiples of ``unit`` (LANE
    or SUBLANE) up to ``cap`` that divide ``dim``, else the whole axis."""
    out = [d for d in range(min(dim, cap) // unit * unit, 0, -unit)
           if dim % d == 0]
    return out or [dim]


def _claim_fn(kernel: str) -> Callable[..., int]:
    # imported lazily: repro.kernels pulls in the backend registry, which
    # imports repro.tuning back — at call time both are fully initialized,
    # at module-import time this would be a cycle (and the analytic tuning
    # path stays import-light, as cost.py promises)
    from repro.kernels.bf16_matmul import vmem_claim_bytes as _bf16_claim
    from repro.kernels.q8_matmul import vmem_claim_bytes as _q8mm_claim
    from repro.kernels.q8_matvec import vmem_claim_bytes as _q8mv_claim

    def bf16_claim(*, k: int = 0, **tiles) -> int:
        return _bf16_claim(**tiles)     # no K-resident state: k is unused

    return {"q8_matmul": _q8mm_claim,
            "q8_matvec": _q8mv_claim,
            "bf16_matmul": bf16_claim}[kernel]


def enumerate_candidates(kernel: str, m: int, n: int, k: int, *,
                         vmem_budget_bytes: int = VMEM_FULL_BYTES,
                         x_bytes: int = 2) -> List[TileCandidate]:
    """All admissible tilings of (M,N,K) for ``kernel`` within the budget.

    Deterministic order (block_k desc, then block_n, block_m desc) so ties
    in the cost model resolve identically across runs and hosts.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")
    if kernel.startswith("q8") and k % QBLOCK:
        return []
    claim = _claim_fn(kernel)
    bns = legal_tiles(lane_padded(n), BLOCK_N_CAP, LANE)
    out: List[TileCandidate] = []
    if kernel == "q8_matvec":
        # the matvec keeps the whole (B, K) activation resident: only the
        # N streaming granularity is tunable; K is a single block.
        for bn in bns:
            v = claim(b=m, k=k, block_n=bn, x_bytes=x_bytes)
            if v <= vmem_budget_bytes:
                out.append(TileCandidate(kernel, m, bn, k, v))
        return out
    for bk in legal_tiles(k, BLOCK_K_CAP, LANE):
        for bn in bns:
            for bm in legal_tiles(m, BLOCK_M_CAP, SUBLANE):
                v = claim(block_m=bm, block_n=bn, block_k=bk, k=k,
                          x_bytes=x_bytes)
                if v <= vmem_budget_bytes:
                    out.append(TileCandidate(kernel, bm, bn, bk, v))
    return out


def default_tiles(kernel: str, m: int, n: int, k: int,
                  block_k: int = 256) -> Tuple[int, int, int]:
    """The (block_m, block_n, block_k) dispatch uses with no tuned tiling:
    the largest legal tiles under fixed caps (block_m 128, block_n 512 on
    the matvec and 256 elsewhere, block_k ``block_k``; the matvec takes K
    whole). ``m`` is the sublane-padded row count.

    ``q8_matmul`` dequantizes every weight tile once per M tile, so where
    M needs more than one 128-row tile it takes the largest legal M tile
    whose VMEM claim fits the tuner's budget instead: the 1504-row Whisper
    encoder is one M tile, where the cap left 47 tiles of 32 rows."""
    if kernel == "q8_matvec":
        return m, legal_tiles(lane_padded(n), 512, LANE)[0], k
    bm = legal_tiles(m, 128, SUBLANE)[0]
    bn = legal_tiles(lane_padded(n), 256, LANE)[0]
    bk = legal_tiles(k, block_k, LANE)[0]
    if kernel == "q8_matmul" and bm < m:
        claim = _claim_fn(kernel)
        bm = next((b for b in legal_tiles(m, m, SUBLANE)
                   if claim(block_m=b, block_n=bn, block_k=bk, k=k)
                   <= VMEM_FULL_BYTES // 2), bm)
    return bm, bn, bk


def default_candidate(kernel: str, m: int, n: int, k: int, *,
                      x_bytes: int = 2) -> TileCandidate:
    """``default_tiles`` as a ``TileCandidate``, so benchmarks
    (tune_sweep's baseline column) and replay features (DESIGN.md §14.1)
    can price the untuned path with the same machinery as tuned ones."""
    claim = _claim_fn(kernel)
    bm, bn, bk = default_tiles(kernel, m, n, k)
    if kernel == "q8_matvec":
        return TileCandidate(kernel, m, bn, k,
                             claim(b=m, k=k, block_n=bn, x_bytes=x_bytes))
    return TileCandidate(kernel, bm, bn, bk,
                         claim(block_m=bm, block_n=bn, block_k=bk, k=k,
                               x_bytes=x_bytes))


def budget_grid(min_kb: int = 16, max_bytes: int = VMEM_FULL_BYTES,
                agg_units: int = 46) -> List[int]:
    """Geometric sweep of VMEM budgets in bytes, from the paper's smallest
    interesting LMM point (16 KB x AGG_UNITS aggregate ≈ 736 KB) up to full
    VMEM — the x-axis of the (local-memory x burst) grid."""
    out = []
    b = min_kb * 1024 * agg_units
    while b < max_bytes:
        out.append(b)
        b *= 2
    out.append(max_bytes)
    return out
