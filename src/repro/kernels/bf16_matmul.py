"""Pallas TPU kernel: 16-bit matmul with f32 accumulation (the paper's FP16
dot-product kernel, §3.2 Fig 5, re-tiled for the MXU).

IMAX converts FP16->FP32 inline on ALU2 and runs 2-way SIMD FMA on a 64-bit
datapath; the MXU does the same job natively on bf16 operands with an f32
accumulator tree (``preferred_element_type=f32``). The tiling mirrors
q8_matmul so the burst (block_k) sweep applies to both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 256


def vmem_claim_bytes(block_m: int = DEFAULT_BLOCK_M,
                     block_n: int = DEFAULT_BLOCK_N,
                     block_k: int = DEFAULT_BLOCK_K,
                     x_bytes: int = 2) -> int:
    """VMEM working set of one grid step (the LMM-sizing analog used by the
    autotuner, DESIGN.md §9.1): double-buffered bf16 x/w tiles + f32
    accumulator scratch + out tile."""
    db = 2  # pallas pipeline double-buffers inputs
    return (db * (block_m * block_k * x_bytes       # x tile
                  + block_n * block_k * 2)          # bf16 weight tile
            + block_m * block_n * 4                 # accumulator scratch
            + block_m * block_n * 4)                # out tile


def _bf16_matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Inline 16->32 conversion happens in the MXU datapath: bf16 operands,
    # f32 accumulation (the IMAX ALU2 analog; DESIGN.md §2).
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bf16_matmul(x: jax.Array, w: jax.Array, *,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                block_k: int = DEFAULT_BLOCK_K,
                interpret: bool = False) -> jax.Array:
    """x (M,K) @ w (N,K)^T -> (M,N) f32. Exact tiling required; ragged sizes
    go through core.mixed_exec."""
    m, k = x.shape
    n, k2 = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(f"({m},{n},{k}) not tiled by "
                         f"({block_m},{block_n},{block_k})")
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _bf16_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, w)
