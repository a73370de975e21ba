"""Pallas TPU kernel: Q8_0 block-dequant matmul (the paper's Q8_0 dot-product
kernel, §3.2 Fig 6, re-tiled for the MXU).

IMAX streams 34-byte Q8_0 blocks through a 46-PE lane with packed int8 MACs
(OP_SML8) and pipeline adds (OP_AD32). The TPU-native mapping (DESIGN.md §2):

* HBM traffic stays int8 + per-block scales — the 2x footprint cut is the
  whole point of the paper's Q8_0 path and directly halves the *memory*
  roofline term for decode.
* Dequantization happens inside VMEM (the LMM analog) right before the MXU
  contraction, like IMAX's inline dequant on ALU3 — no dedicated conversion
  pass, no dequantized weights ever resident in HBM.
* The grid pipelines HBM->VMEM copies against compute (the LMM's
  hardware-managed double buffering).
* ``block_k`` is the burst-length analog; it must divide by 32 (whole Q8_0
  blocks per burst — the paper picks bursts holding whole packed words).
* Per-block scales are spread across their 32 columns by a 0/1
  block-expansion matrix on the MXU (``expand_scales``), not by a
  ``(bn, bk) -> (bn, bk//32, 32)`` reshape: Mosaic has no layout for that
  sub-lane shape cast, so the reshape form never compiles for a TPU.

Layouts:
  x:      (M, K)   bf16/f32 activations
  qs:     (N, K)   int8   (Q8_0 payload, blocks flattened)
  scales: (N, K//32) f32  (fp16-valued; one (block_n, K//32) row band
          stays resident across the K sweep — a ``block_k//32``-wide
          scales block would be neither a lane multiple nor the whole dim)
  out:    (M, N)   f32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qformats import QBLOCK

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 256   # burst analog; VMEM claim scales with it


def expand_scales(s: jax.Array, first_block, width: int) -> jax.Array:
    """Per-column scales of a ``width``-column K window: ``out[n, c] =
    s[n, first_block + c // 32]``, for ``s`` of shape (bn, nb).

    The expansion is a product with a 0/1 (nb, width) matrix built from
    iotas, so it needs no sub-lane reshape and no dynamic lane slice. The
    scales go through the MXU as a bf16 high part plus a bf16 low part:
    an fp16-valued scale (11 significant bits) is their exact sum, so the
    result is exact whatever precision the MXU gives f32 operands."""
    nb = s.shape[1]
    blk = jax.lax.broadcasted_iota(jnp.int32, (nb, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, width), 1)
    e = (blk == first_block + col // QBLOCK).astype(jnp.bfloat16)
    hi = s.astype(jnp.bfloat16)
    lo = (s - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def spread(part):
        return jax.lax.dot_general(part, e, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return spread(hi) + spread(lo)


def _q8_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    """One (i, j, k) grid step: acc += x_tile @ dequant(q_tile, s_band)^T."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                  # (bm, bk)
    q = q_ref[...]                                      # (bn, bk) int8
    bk = q.shape[1]
    # In-VMEM block dequant: this step's K window of the resident scales
    s = expand_scales(s_ref[...], kk * (bk // QBLOCK), bk)   # (bn, bk)
    w = q.astype(jnp.float32) * s
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def q8_matmul(x: jax.Array, qs: jax.Array, scales: jax.Array, *,
              block_m: int = DEFAULT_BLOCK_M,
              block_n: int = DEFAULT_BLOCK_N,
              block_k: int = DEFAULT_BLOCK_K,
              interpret: bool = False) -> jax.Array:
    """x (M,K) x Q8_0 W (N,K) -> (M,N) f32. Shapes must tile exactly —
    callers route ragged sizes through core.mixed_exec (the paper's
    main/residual split), so the kernel never sees a partial burst."""
    m, k = x.shape
    n, k2 = qs.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    if block_k % QBLOCK:
        raise ValueError("block_k must hold whole Q8_0 blocks")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(f"({m},{n},{k}) not tiled by "
                         f"({block_m},{block_n},{block_k})")
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _q8_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((block_n, k // QBLOCK), lambda i, j, kk: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, qs, scales)


def scales_band_bytes(block_n: int, k: int) -> int:
    """VMEM of one resident (block_n, K//32) f32 scales band: the lane
    axis pads to 128, so the band costs block_n x 128 lanes per 128
    blocks of K (at least one lane tile)."""
    nb = k // QBLOCK
    return block_n * (-(-nb // 128) * 128) * 4


def vmem_claim_bytes(block_m: int = DEFAULT_BLOCK_M,
                     block_n: int = DEFAULT_BLOCK_N,
                     block_k: int = DEFAULT_BLOCK_K,
                     x_bytes: int = 2, k: int = 0) -> int:
    """The VMEM working set this tiling claims (the LMM-sizing analog):
    double-buffered x/q tiles and scales band (``k`` is the full
    contraction; 0 means a single K block, ``k = block_k``) + the f32
    copy the kernel makes of a narrower x tile + the f32 dequantized tile
    + f32 accumulator + out tile. A bf16 x tile and its f32 copy claim
    what a double-buffered f32 x tile does, so the claim ranks tilings
    the same whichever the activations are."""
    db = 2  # pallas pipeline double-buffers inputs
    x_f32_copy = block_m * block_k * 4 if x_bytes < 4 else 0
    return (db * (block_m * block_k * x_bytes            # x tile
                  + block_n * block_k                    # int8 payload
                  + scales_band_bytes(block_n, k or block_k))
            + x_f32_copy                                 # x.astype(f32)
            + block_n * block_k * 4                      # dequantized tile
            + block_m * block_n * 4                      # accumulator
            + block_m * block_n * 4)                     # out tile
