"""The Pallas TPU backend: the paper's accelerator path (DESIGN.md §12).

Owns everything that used to live inline in ``kernels/ops.py``: sublane
padding of M, lane padding of N, matvec-vs-matmul selection for skinny
decode batches, and tile resolution (explicit plan tiling > tuner cache >
chip-legal defaults, DESIGN.md §10.1 / §9.4 / §6.3). Off-TPU the same
kernels run ``interpret=True`` for correctness tests; on a TPU they run
natively and an explicit ``interpret=True`` is refused. The backend only
*volunteers* (``auto``) on a real TPU — elsewhere it must be pinned
explicitly.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.backends import platform
from repro.backends.base import KERNELS, MAIN, KernelRequest
from repro.core.qformats import QBLOCK, QTensor
from repro.kernels.bf16_matmul import bf16_matmul
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.q8_matvec import q8_matvec
from repro.sharding import ctx
from repro.tuning import kernel_for, space


def _pad_rows(a: jax.Array, mult: int) -> jax.Array:
    pad = (-a.shape[0]) % mult
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


def _tuned(tuner, kernel: str, m: int, n: int, k: int, dtype: str):
    """Winning tiling for the *main-segment* shape, or None (tuner absent or
    nothing admissible under its VMEM budget)."""
    if tuner is None:
        return None
    return tuner.best_tiling(kernel, m, n, k, dtype)


def _block_shape(rec) -> Tuple[int, int, int]:
    """Normalize a tiling source — TuningRecord or plan-entry tuple."""
    if isinstance(rec, tuple):
        return rec
    return rec.block_m, rec.block_n, rec.block_k


def _tiles(kernel: str, mp: int, n: int, k: int, dtype: str, *, block_k: int,
           tuner, tiling) -> Tuple[int, int, int]:
    """Tile shapes in precedence order: an explicit ``tiling`` — a
    trace-time plan entry's resolved ``(block_m, block_n, block_k)``
    (DESIGN.md §10.1) — else a tuner-cache lookup (DESIGN.md §9.4), else
    the chip-legal defaults of ``tuning.space.default_tiles``."""
    dflt = space.default_tiles(kernel, mp, n, k, block_k=block_k)
    rec = tiling or _tuned(tuner, kernel, mp, n, k, dtype)
    if not rec:
        return dflt
    bm, bn, bk = _block_shape(rec)
    # a tiling planned for the whole batch may not divide one device's
    # row shard (see _per_shard); block_m then takes the legal default
    return (bm if mp % bm == 0 else dflt[0]), bn, bk


def m_tiles(entry) -> int:
    """How many M tiles the kernel sweeps for a plan entry's main segment,
    tiled as ``OffloadEngine.execute`` runs it: the entry's tiling, else
    the defaults. A ``q8_matmul`` dequantizes every weight tile once per
    M tile."""
    mp = space.round_up(entry.m, space.SUBLANE)
    bm = _tiles(entry.kernel, mp, entry.n, entry.k_main, entry.dtype,
                block_k=KernelRequest.block_k, tuner=None,
                tiling=entry.tiling)[0]
    return mp // bm


def _padded(x2d: jax.Array, w_rows, bn: int):
    """Pad activations to the sublane multiple and weight rows to a lane
    multiple of ``bn``, so the kernel only sees whole, chip-legal tiles
    (the caller slices the (m, n) result back out)."""
    return (_pad_rows(x2d, space.SUBLANE),
            [_pad_rows(a, math.lcm(space.LANE, bn)) for a in w_rows])


def q8_main(x2d: jax.Array, wq: QTensor, *, interpret: bool,
            block_k: int, tuner=None, tiling=None) -> jax.Array:
    """Aligned-segment Q8_0 path: matvec variant for skinny M, tiled matmul
    otherwise."""
    qs2d = wq.flat_qs()
    n, k = qs2d.shape
    m = x2d.shape[0]
    mp = space.round_up(m, space.SUBLANE)
    kern = kernel_for(m, quantized=True)
    bm, bn, bk = _tiles(kern, mp, n, k, "q8_0", block_k=block_k,
                        tuner=tuner, tiling=tiling)
    xp, (qp, sp) = _padded(x2d, (qs2d, wq.scales), bn)
    if kern == "q8_matvec":
        out = q8_matvec(xp, qp, sp, block_n=bn, interpret=interpret)
    else:
        out = q8_matmul(xp, qp, sp, block_m=bm, block_n=bn, block_k=bk,
                        interpret=interpret)
    return out[:m, :n]


def bf16_main(x2d: jax.Array, w: jax.Array, *, interpret: bool,
              block_k: int, tuner=None, tiling=None) -> jax.Array:
    n, k = w.shape
    m = x2d.shape[0]
    mp = space.round_up(m, space.SUBLANE)
    bm, bn, bk = _tiles("bf16_matmul", mp, n, k, "bf16", block_k=block_k,
                        tuner=tuner, tiling=tiling)
    xp, (wp,) = _padded(x2d, (w,), bn)
    return bf16_matmul(xp, wp, block_m=bm, block_n=bn, block_k=bk,
                       interpret=interpret)[:m, :n]


def _per_shard(fn, mesh):
    """Run a kernel wrapper under ``shard_map`` on a serving mesh: GSPMD
    cannot partition a Mosaic kernel. Rows split over the batch axes
    (slot-DP, DESIGN.md §13) where every shard gets whole sublane tiles,
    and are replicated otherwise (batch-1 prefill); weights replicate,
    as ``serve_param_specs`` places them on a data-only mesh."""
    axes = tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    size = math.prod(mesh.shape[a] for a in axes)

    def run(x2d, w):
        split = axes and x2d.shape[0] % (size * space.SUBLANE) == 0
        rows = P(axes if split else None, None)
        return jax.shard_map(fn, mesh=mesh,
                             in_specs=(rows, jax.tree.map(lambda _: P(), w)),
                             out_specs=rows, check_vma=False)(x2d, w)
    return run


class PallasTPUBackend:
    """Accelerator kernels — native on TPU, ``interpret=True`` elsewhere."""

    name = "pallas_tpu"

    def supports(self, req: KernelRequest) -> bool:
        # main segments only: the residual tail is by construction ragged
        # (its whole reason to exist is that it doesn't tile) and belongs
        # to the host path
        if req.segment != MAIN or req.kernel not in KERNELS:
            return False
        if req.dtype == "q8_0" and req.k % QBLOCK != 0:
            return False
        return True

    def auto(self, req: KernelRequest) -> bool:
        return self.supports(req) and platform.on_tpu()

    def _interpret(self, req: KernelRequest) -> bool:
        if req.interpret and platform.on_tpu():
            # the interpreter would run the kernels as slow XLA emulation
            # on the chip while every report still says pallas_tpu
            raise ValueError("interpret=True requested on a TPU; leave "
                             "interpret unset to run the kernels natively")
        return (req.interpret if req.interpret is not None
                else platform.default_interpret())

    def build(self, req: KernelRequest):
        kw = dict(interpret=self._interpret(req), block_k=req.block_k,
                  tuner=req.tuner, tiling=req.tiling)
        fn = functools.partial(q8_main if req.dtype == "q8_0" else bf16_main,
                               **kw)
        mesh = ctx.current_mesh()
        return fn if mesh is None or mesh.size == 1 else _per_shard(fn, mesh)

    def cost_hints(self, req: KernelRequest):
        return {"flops": req.flops, "unit": "MXU",
                "native": platform.on_tpu(),
                "interpret": self._interpret(req)}
