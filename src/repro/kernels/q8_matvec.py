"""Pallas TPU kernel: decode-path Q8_0 matvec (the paper's per-token
dot-product: M=1 activations against a quantized weight matrix).

Decode is the regime the paper profiles hardest (the decoder dominates
invocation counts) and on TPU it is *memory-bound*: arithmetic intensity of a
(B<=8, K) x (N, K) contraction is ~B FLOPs/byte, far below the 240 FLOP/byte
v5e ridge. The kernel therefore optimizes HBM bytes, not MXU utilization:

* weights stream as int8 + scales (the Q8_0 2x cut — the paper's point),
* the activation tile is loaded once and kept VMEM-resident across the whole
  N sweep (grid iterates N only; K is a single block),
* the batch dim pads to the 8-sublane minimum in the backend wrapper,
* per-block scales spread over their columns on the MXU
  (``q8_matmul.expand_scales``) — no sub-lane reshape, which Mosaic
  cannot lay out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qformats import QBLOCK
from repro.kernels.q8_matmul import expand_scales, scales_band_bytes

DEFAULT_BLOCK_N = 512


def vmem_claim_bytes(b: int = 8, k: int = 384,
                     block_n: int = DEFAULT_BLOCK_N,
                     x_bytes: int = 2) -> int:
    """VMEM working set of one grid step (autotuner input, DESIGN.md §9.1):
    the whole (B, K) activation stays resident across the N sweep; the int8
    payload + scales tiles double-buffer; the f32 dequantized tile lives
    for one step; the out tile is written per step."""
    db = 2
    return (b * k * x_bytes                          # resident activation
            + db * (block_n * k                      # int8 payload tile
                    + scales_band_bytes(block_n, k))  # scales tile
            + block_n * k * 4                        # dequantized tile
            + b * block_n * 4)                       # out tile


def _q8_matvec_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                  # (B, K) resident
    q = q_ref[...]                                      # (bn, K) int8
    w = q.astype(jnp.float32) * expand_scales(s_ref[...], 0, q.shape[1])
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def q8_matvec(x: jax.Array, qs: jax.Array, scales: jax.Array, *,
              block_n: int = DEFAULT_BLOCK_N,
              interpret: bool = False) -> jax.Array:
    """x (B, K) x Q8_0 W (N, K) -> (B, N) f32; B small (decode batch tile)."""
    b, k = x.shape
    n, k2 = qs.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} not tiled by block_n={block_n}")
    grid = (n // block_n,)
    return pl.pallas_call(
        _q8_matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),            # resident
            pl.BlockSpec((block_n, k), lambda j: (j, 0)),      # streamed
            pl.BlockSpec((block_n, k // QBLOCK), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, qs, scales)
