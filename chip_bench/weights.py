"""Seeded Whisper weights, made on the device in one jitted call.

The benchmark makes the weights itself, from ``--seed``, in the layout
``repro.models.whisper.init_whisper`` defines and in the configuration's
parameter dtype (bf16): the served path quantises them to Q8_0 as it would
a checkpoint, and the float32 reference reads the same values back. The
program's own initialiser is never called for values; its abstract shapes
are only compared against, so a layout change in the program fails loudly
here instead of feeding the reference a tree it misreads.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: rows of both positional tables: the 1500-frame encoder window bounds
#: every position the encoder or a <= 448-token decoder reads
POSITIONS = 1500


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, also ones past 32 bits."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def sinusoids(n: int, d: int) -> np.ndarray:
    """The repo's fixed encoder table (``layers.sinusoidal_positions``)."""
    pos = np.arange(n, dtype=np.float64)[:, None]
    dim = np.arange(d // 2, dtype=np.float64)[None, :]
    ang = pos * np.exp(-math.log(10_000.0) * dim / (d // 2 - 1 + 1e-9))
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)


def _tree(cfg, draw) -> dict:
    """The parameter tree of one Whisper model; ``draw(shape, kind)``
    gives each leaf. Kinds: ``w`` (a (out, in) matrix), ``bias``,
    ``scale`` (a norm gain), ``table`` (token embedding), ``dec_pos``."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    hd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim

    def lin(n_in, n_out, bias, lead):
        p = {"w": draw((*lead, n_out, n_in), "w")}
        if bias:
            p["b"] = draw((*lead, n_out), "bias")
        return p

    def norm(lead):
        return {"scale": draw((*lead, d), "scale"),
                "bias": draw((*lead, d), "bias")}

    def attn(lead):
        b = cfg.qkv_bias
        return {"q": lin(d, hd, b, lead), "k": lin(d, kvd, b, lead),
                "v": lin(d, kvd, b, lead), "o": lin(hd, d, False, lead)}

    def ffn(lead):
        return {"up": lin(d, f, False, lead), "down": lin(f, d, False, lead)}

    el, dl = (cfg.num_encoder_layers,), (cfg.num_layers,)
    return {
        "frontend": lin(cfg.n_mels, d, True, ()),
        "enc_pos": {"table": draw((POSITIONS, d), "enc_pos")},
        "enc_blocks": {"norm1": norm(el), "attn": attn(el),
                       "norm2": norm(el), "ffn": ffn(el)},
        "enc_norm": norm(()),
        "embed": {"table": draw((v, d), "table")},
        "dec_pos": {"table": draw((POSITIONS, d), "dec_pos")},
        "dec_blocks": {"norm1": norm(dl), "self_attn": attn(dl),
                       "norm_x": norm(dl), "cross_attn": attn(dl),
                       "norm2": norm(dl), "ffn": ffn(dl)},
        "dec_norm": norm(()),
    }


def make_params(cfg, seed: int) -> dict:
    """The model's weights for ``seed``, on the default device, in
    ``cfg.param_dtype``, from one jitted call."""
    dtype = jnp.dtype(cfg.param_dtype)
    table = jnp.asarray(sinusoids(POSITIONS, cfg.d_model), jnp.float32)

    def build(key):
        counter = iter(range(1 << 20))

        def draw(shape, kind):
            if kind == "enc_pos":
                return table.astype(dtype)
            k = jax.random.fold_in(key, next(counter))
            z = jax.random.normal(k, shape, jnp.float32)
            scale = {"w": shape[-1] ** -0.5, "bias": 0.02, "scale": 0.1,
                     "table": 0.02, "dec_pos": 0.01}[kind]
            return (1.0 + scale * z if kind == "scale"
                    else scale * z).astype(dtype)
        return _tree(cfg, draw)

    return jax.jit(build)(seed_key(seed))


def check_layout(cfg, params) -> None:
    """Raise if ``params`` is not laid out as the program's initialiser
    lays out this configuration (shapes and dtypes, leaf by leaf)."""
    from repro.models import model as model_lib
    want = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg, max_positions=POSITIONS),
        jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError("benchmark weight layout differs from the "
                         f"program's: {got} != {want}")
