"""What every family's float32 reference shares: the layer helpers, the
control's rounding, and the gap a served token is judged by.

Each family module (``families/<name>.py``) holds the plain float32
forward of its model, written from the program's definition and importing
nothing of the program; it runs at the ``highest`` matmul precision.
``quantize_blocks`` makes the control: the same forward on weights rounded
to a lower precision in blocks of 32 along K, as Q8_0 rounds them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 32


def linear(p, x):
    """``x @ w.T``, plus the bias where the layer has one."""
    y = x @ p["w"].T
    return y + p["b"] if "b" in p else y


def layer(tree, i):
    """Layer ``i`` of a stack whose leaves carry the layers on axis 0."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def is_matrix(path) -> bool:
    """The leaves the served path stores in Q8_0 where their last axis
    holds whole blocks: every weight matrix and the token table; norms,
    biases and positional tables stay dense."""
    names = [str(getattr(k, "key", k)) for k in path]
    return names[-1] in ("w", "table") and not any("pos" in n for n in names)


def quantize_blocks(w: jax.Array, bits: int) -> jax.Array:
    """Round ``w`` to signed ``bits``-bit integers in blocks of 32 along
    the last axis, one scale per block (amax over the block's largest
    code, stored in fp16 as Q8_0 stores it); returns the float values the
    rounded weights stand for."""
    top = 2 ** (bits - 1) - 1
    lead, k = w.shape[:-1], w.shape[-1]
    b = w.astype(jnp.float32).reshape(*lead, k // BLOCK, BLOCK)
    d = (jnp.max(jnp.abs(b), -1, keepdims=True) / top
         ).astype(jnp.float16).astype(jnp.float32)
    q = b / jnp.where(d > 0, d, 1.0)
    q = jnp.clip(jnp.sign(q) * jnp.floor(jnp.abs(q) + 0.5), -top, top)
    return (q * d).reshape(w.shape)


@functools.partial(jax.jit, static_argnames=("bits",))
def quantized(p, bits: int):
    """``p`` with every Q8_0-stored leaf rounded to ``bits`` bits."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (quantize_blocks(a, bits)
                         if is_matrix(path) and a.shape[-1] % BLOCK == 0
                         else a), p)


def served_gaps(ref_logits: np.ndarray, served: np.ndarray,
                vocab: int) -> np.ndarray:
    """Per position, how far the served token's reference logit lies below
    the reference's best over the true vocabulary."""
    ref = ref_logits[:, :vocab]
    return ref.max(-1) - np.take_along_axis(ref, served[:, None], 1)[:, 0]
