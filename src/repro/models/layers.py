"""Shared building blocks: norms, rotary embeddings, linear/MLP, embeddings.

Functional style: ``init_*`` returns a param dict; ``*_apply`` consumes it.
Weights are stored (out_features, in_features) — the kernels' W[N, K] layout.
The linear path is pluggable: training/dry-run uses the XLA contraction;
serving can route through core.offload.OffloadEngine with Q8_0 weights.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qformats import QTensor


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------
def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.bfloat16, scale: Optional[float] = None) -> dict:
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_out, d_in), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def per_layer(engine, n: int):
    """Context for tracing the body of a ``lax.scan``/``vmap`` over ``n``
    stacked layers: an offload engine then counts each linear inside once
    per layer (``OffloadEngine.repeat``, DESIGN.md §10.2)."""
    return engine.repeat(n) if engine is not None else nullcontext()


def linear(p: dict, x: jax.Array, engine=None, name: str = "linear") -> jax.Array:
    """y = x @ W^T (+ b). ``engine`` routes through the offload dispatcher
    (paper path: Q8_0/bf16 Pallas kernel main + host residual). The engine
    path is trace-pure (DESIGN.md §10.1) — routing resolves from static
    shapes and the static ``name`` identifies the call site in recorded
    dispatch plans — so callers may sit inside ``jax.jit`` freely."""
    w = p["w"]
    if engine is not None:
        y = engine.linear(x, w, name=name).astype(x.dtype)
    elif isinstance(w, QTensor):
        # XLA dequant path (same math as kernels/ref.py)
        wd = (w.qs.astype(jnp.float32) * w.scales[..., None]).reshape(w.shape)
        y = jax.lax.dot_general(x, wd.astype(x.dtype),
                                (((x.ndim - 1,), (1,)), ((), ())))
    else:
        y = jax.lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(d: int, kind: str, dtype=jnp.bfloat16) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32)
    if "bias" in p:
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal table (n, d)."""
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-jnp.log(10_000.0) * dim / (d // 2 - 1 + 1e-9))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, d_ff: int, act: str, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    p = {"up": init_linear(ks[0], d, d_ff, dtype=dtype),
         "down": init_linear(ks[1], d_ff, d, dtype=dtype)}
    if act == "swiglu":
        p["gate"] = init_linear(ks[2], d, d_ff, dtype=dtype)
    return p


def mlp_apply(p: dict, x: jax.Array, act: str, engine=None) -> jax.Array:
    up = linear(p["up"], x, engine, "ffn.up")
    if act == "swiglu":
        gate = linear(p["gate"], x, engine, "ffn.gate")
        h = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    else:
        h = jax.nn.gelu(up.astype(jnp.float32))
    return linear(p["down"], h.astype(x.dtype), engine, "ffn.down")


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
                      ).astype(dtype)}


def embed(p: dict, ids: jax.Array) -> jax.Array:
    t = p["table"]
    if isinstance(t, QTensor):
        # row-wise dequant of the Q8_0 table (whisper.cpp quantizes the
        # token embedding; lookups dequantize only the gathered rows)
        qs = jnp.take(t.qs, ids, axis=0)          # (..., K/32, 32)
        sc = jnp.take(t.scales, ids, axis=0)      # (..., K/32)
        rows = qs.astype(jnp.float32) * sc[..., None]
        return rows.reshape(*ids.shape, t.k)
    return jnp.take(t, ids, axis=0)


def unembed(p: dict, x: jax.Array, engine=None) -> jax.Array:
    """Tied readout: logits = x @ table^T (the paper's ``dec.vocab`` kernel
    class — its single largest dot-product)."""
    t = p["table"]
    if engine is not None or isinstance(t, QTensor):
        return linear({"w": t}, x, engine, "dec.vocab")
    return jax.lax.dot_general(x, t, (((x.ndim - 1,), (1,)), ((), ())))
