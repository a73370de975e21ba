"""Plain float32 reference of the Whisper encoder-decoder as the repo
defines it (``repro.models.whisper``), written from that definition and
importing nothing of the program.

What it computes, in float32 at the ``highest`` matmul precision:

* conv frontend stub: one linear (with bias) on the mel frames, tanh GELU,
  plus the fixed sinusoidal table;
* encoder: pre-LayerNorm blocks of non-causal attention (q/k/v with bias,
  output projection without) and a bias-free GELU MLP, then a final
  LayerNorm;
* cross-KV: each decoder layer's k/v projection of the encoder output;
* decoder, teacher-forced: token embedding plus the learned positional
  row, pre-LayerNorm blocks of causal self-attention, cross-attention and
  the MLP, a final LayerNorm, and the tied vocabulary readout.

Departures from published Whisper, shared with the program: the conv stub,
the k-projection bias (zero in the checkpoints, random here: it shifts
every key's score by the same amount, so softmax ignores it), bias-free
MLPs and output projections, and the tanh form of GELU.

``quantize_blocks`` makes the control: the same forward on weights rounded
to a lower precision in blocks of 32 along K, as Q8_0 rounds them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
BLOCK = 32


def _ln(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _lin(p, x):
    y = x @ p["w"].T
    return y + p["b"] if "b" in p else y


def _attend(q, k, v, heads, mask=None):
    """q (S, H*D), k/v (T, H*D) -> (S, H*D)."""
    s, t = q.shape[0], k.shape[0]
    q = q.reshape(s, heads, -1)
    k = k.reshape(t, heads, -1)
    v = v.reshape(t, heads, -1)
    logits = jnp.einsum("shd,thd->hst", q, k) * q.shape[-1] ** -0.5
    if mask is not None:
        logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)


def _mlp(p, x):
    return jax.nn.gelu(x @ p["up"]["w"].T) @ p["down"]["w"].T


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def encode(p, mel, heads: int):
    """mel (F, n_mels) -> encoder output (F, d)."""
    x = jax.nn.gelu(_lin(p["frontend"], mel))
    x = x + p["enc_pos"]["table"][:mel.shape[0]]
    n = p["enc_blocks"]["norm1"]["scale"].shape[0]
    for i in range(n):
        b = _layer(p["enc_blocks"], i)
        h = _ln(b["norm1"], x)
        a = b["attn"]
        x = x + _lin(a["o"], _attend(_lin(a["q"], h), _lin(a["k"], h),
                                     _lin(a["v"], h), heads))
        x = x + _mlp(b["ffn"], _ln(b["norm2"], x))
    return _ln(p["enc_norm"], x)


def decode(p, memory, tokens, heads: int):
    """Teacher-forced decoder: tokens (L,) i32 -> logits (L, padded vocab);
    row j is the next-token distribution after tokens[:j+1]."""
    n_tok = tokens.shape[0]
    x = p["embed"]["table"][tokens] + p["dec_pos"]["table"][:n_tok]
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))
    n = p["dec_blocks"]["norm1"]["scale"].shape[0]
    for i in range(n):
        b = _layer(p["dec_blocks"], i)
        h = _ln(b["norm1"], x)
        a = b["self_attn"]
        x = x + _lin(a["o"], _attend(_lin(a["q"], h), _lin(a["k"], h),
                                     _lin(a["v"], h), heads, causal))
        h = _ln(b["norm_x"], x)
        c = b["cross_attn"]
        x = x + _lin(c["o"], _attend(_lin(c["q"], h), _lin(c["k"], memory),
                                     _lin(c["v"], memory), heads))
        x = x + _mlp(b["ffn"], _ln(b["norm2"], x))
    return _ln(p["dec_norm"], x) @ p["embed"]["table"].T


@functools.partial(jax.jit, static_argnames=("heads",))
def logits(p, mel, tokens, heads: int):
    """Reference logits of one request: its padded mel (F, n_mels) and its
    decoder input (SOT then the served tokens, padded to a fixed length)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        return decode(p, encode(p, mel.astype(jnp.float32), heads),
                      tokens, heads)


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
