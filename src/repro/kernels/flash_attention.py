"""Pallas TPU kernel: flash-2 attention forward (the beyond-paper §Perf
optimization, as a VMEM-tiled kernel rather than an XLA-level scan).

models/attention.py's ``_flash_attention`` expresses the k-blocked online
softmax at the jnp level so the 512-device dry-run can lower it on CPU;
THIS kernel is what the schedule compiles to on a real TPU: q tiles stay
VMEM-resident across the k sweep (the LMM-residency idea from the paper's
double-buffered operand streaming), k/v tiles stream HBM->VMEM through the
pallas pipeline, and the (block_q, block_k) score tile never touches HBM.

Grid: (batch*heads, Sq/block_q, Sk/block_k) with the k axis innermost
("arbitrary") carrying running (m, l, acc) in VMEM scratch.

Layouts:  q (BH, Sq, D) | k,v (BH, Sk, D) -> out (BH, Sq, D) f32.
The ops wrapper folds (B, H) and handles GQA head repetition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, scale, causal,
                      block_q, block_k):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                      # (bq, d)
    k = k_ref[0]                                      # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q (BH, Sq, D) x k,v (BH, Sk, D) -> (BH, Sq, D) f32.

    Exact tiling required (ragged sizes go through the jnp path — the same
    main/residual contract as the matmul kernels)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"(Sq={sq}, Sk={sk}) not tiled by "
                         f"({block_q}, {block_k})")
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(_flash_fwd_kernel, scale=d ** -0.5,
                               causal=causal, block_q=block_q,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)


def vmem_claim_bytes(block_q: int = DEFAULT_BLOCK_Q,
                     block_k: int = DEFAULT_BLOCK_K,
                     d: int = 128, in_bytes: int = 2) -> int:
    """VMEM working set (the LMM-sizing analog): double-buffered q/k/v
    tiles + f32 stats/acc scratch + out tile."""
    db = 2
    return (db * (block_q * d * in_bytes + 2 * block_k * d * in_bytes)
            + block_q * (2 + d) * 4        # m, l, acc scratch
            + block_q * d * 4)             # out tile
