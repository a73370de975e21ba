"""The Whisper encoder-decoder family, as the repo defines it
(``repro.models.whisper``): what the harness needs to know of it.

A request is a log-mel spectrogram of ``size`` frames, padded by the
scheduler to the pool's window of ``cfg.encoder_ctx`` frames; decoding
starts from the SOT token. The float32 reference below is written from the
program's definition and imports nothing of the program. What it computes,
in float32 at the ``highest`` matmul precision:

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
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import spec, weights
from chip_bench.reference import layer as _layer
from chip_bench.reference import linear as _lin

SOT = 1
EPS = 1e-5
#: rows of both positional tables: the 1500-frame encoder window bounds
#: every position the encoder or a <= 448-token decoder reads
POSITIONS = 1500
#: the scopes ``models/whisper.py`` gives its modules (``profile_cell.py``)
SCOPES = ("encoder/conv", "encoder/self_attn", "encoder/ffn", "cross_kv",
          "decoder/self_attn", "decoder/cross_attn", "decoder/ffn",
          "readout")


def model_config(conf: dict):
    """The program's ``ModelConfig``, checked against the sizes of the
    published ``config.json`` the file states."""
    from repro.configs.registry import get_config
    cfg = get_config(conf["arch"])
    return spec.published(conf, cfg, {
        "d_model": cfg.d_model, "encoder_layers": cfg.num_encoder_layers,
        "decoder_layers": cfg.num_layers,
        "encoder_attention_heads": cfg.num_heads,
        "decoder_attention_heads": cfg.num_heads,
        "encoder_ffn_dim": cfg.d_ff, "decoder_ffn_dim": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "num_mel_bins": cfg.n_mels,
        "max_source_positions": cfg.encoder_ctx})


# -- weights -----------------------------------------------------------------

def sinusoids(n: int, d: int) -> np.ndarray:
    """The repo's fixed encoder table (``layers.sinusoidal_positions``)."""
    pos = np.arange(n, dtype=np.float64)[:, None]
    dim = np.arange(d // 2, dtype=np.float64)[None, :]
    ang = pos * np.exp(-math.log(10_000.0) * dim / (d // 2 - 1 + 1e-9))
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)


def _tree(cfg, draw) -> dict:
    """The parameter tree of one Whisper model; ``draw(shape, kind)``
    gives each leaf. Kinds: ``w`` (a (out, in) matrix), ``bias``,
    ``scale`` (a norm gain), ``table`` (token embedding), ``dec_pos``,
    ``enc_pos`` (the fixed table)."""
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
    return weights.make(cfg, seed, _tree, scales={"dec_pos": 0.01},
                        fixed={"enc_pos": sinusoids(POSITIONS, cfg.d_model)})


# -- requests ----------------------------------------------------------------

def payload(seed: int, index: int, size: int, cfg) -> np.ndarray:
    """Request ``index``'s audio: ``size`` Gaussian log-mel frames from the
    seed and the index."""
    rng = np.random.default_rng([seed % 2**64, 2, index])
    return rng.standard_normal((size, cfg.n_mels), np.float32)


def program_args(cfg, size: int) -> jax.ShapeDtypeStruct:
    """The prefill's input for a request of ``size`` frames: every clip is
    padded to the pool's window, so one shape serves them all."""
    return jax.ShapeDtypeStruct((1, cfg.encoder_ctx, cfg.n_mels),
                                jnp.float32)


def submit_kwargs(cfg) -> dict:
    return {"sot_id": SOT}


def scheduler_kwargs(cfg) -> dict:
    return {"n_frames": cfg.encoder_ctx}


# -- the float32 reference ---------------------------------------------------

def _ln(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


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


def reference_logits(params, cfg, payload, first, tokens, pad_to: int):
    """The reference's rows that predict each served token, and those
    tokens: the decoder is fed SOT and then the served tokens (``first``,
    the SOT the program fed, is the request's and is not judged)."""
    mel = np.zeros((cfg.encoder_ctx, cfg.n_mels), np.float32)
    mel[:payload.shape[0]] = payload
    toks = np.asarray(tokens, np.int32)
    inp = np.zeros((pad_to,), np.int32)
    inp[0] = SOT
    inp[1:len(toks)] = toks[:-1]
    rows = np.asarray(logits(params, mel, inp, cfg.num_heads))[:len(toks)]
    return rows, toks


# -- model FLOPs -------------------------------------------------------------
# The FLOPs the Whisper forward needs, whatever runs it: a prefill (encoder
# over the padded window, plus every decoder layer's cross-K/V projection)
# and one decoded token at a given context (self-attention over ``ctx``
# positions, cross-attention over the window, the MLP and the vocabulary
# readout). Every request is padded to the window, so the window, not the
# clip, is counted.

def prefill_flops(cfg, size: int) -> int:
    """Encoder over the window's positions plus each decoder layer's
    cross-K/V projection of its output."""
    d, f = cfg.d_model, cfg.encoder_ctx
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    front = 2 * f * cfg.n_mels * d
    layer = (2 * f * d * (qd + 2 * kvd) + 2 * f * qd * d     # q, k, v, o
             + 2 * 2 * f * f * qd                            # scores, PV
             + 2 * 2 * f * d * cfg.d_ff)                     # MLP
    cross = 2 * f * d * 2 * kvd
    return front + cfg.num_encoder_layers * layer + cfg.num_layers * cross


def token_flops(cfg, ctx: int, size: int) -> int:
    """One decoded token whose self-attention sees ``ctx`` positions."""
    d, frames = cfg.d_model, cfg.encoder_ctx
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    layer = (2 * d * (qd + 2 * kvd) + 2 * qd * d              # self q,k,v,o
             + 2 * 2 * ctx * qd                               # self attn
             + 2 * d * qd + 2 * qd * d                        # cross q, o
             + 2 * 2 * frames * qd                            # cross attn
             + 2 * 2 * d * cfg.d_ff)                          # MLP
    return cfg.num_layers * layer + 2 * d * cfg.vocab_size
