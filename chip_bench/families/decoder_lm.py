"""The dense decoder-only LM family, as the program defines it
(``repro.models.model`` ``_TokenLM`` over ``repro.models.transformer``'s
stack): what the harness needs to know of it.

A request is a prompt of ``size`` token ids; the program prefills it and
its greedy pick after the prompt is the first decode input, so that pick
is a served token here beside the ones the decode steps emit. The float32
reference below is written from the program's definition and imports
nothing of the program. What it computes, in float32 at the ``highest``
matmul precision, teacher-forced over the prompt and the served tokens:

* the token embedding (no positional table);
* per layer, pre-norm (RMSNorm, or LayerNorm with a bias) blocks of causal
  grouped-query attention (q/k/v with the optional bias, RoPE on q and k
  over each head's two halves, every ``heads / kv_heads`` query heads
  sharing one k/v head, the output projection without bias) and the MLP
  (SwiGLU: silu(gate) x up, then down; or tanh-GELU of up, then down,
  all without bias);
* a final norm and the readout: the token table (tied) or ``lm_head``.

Departures from published models, shared with the program: every norm's
epsilon is 1e-5 whatever a ``config.json`` states (Qwen2.5 states
``rms_norm_eps`` 1e-6); the GELU is the tanh form; no rotary scaling or
sliding window. Only the ``dense`` family is computed: MoE, SSM, hybrid
and vision layers, the int8 KV cache and learned positions are refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import spec, weights
from chip_bench.reference import layer, linear

EPS = 1e-5
#: the LM's initialiser takes no positional table
POSITIONS = 0
#: the scopes the program gives its modules: the decoder stack names none
SCOPES = ()
#: a reference input is padded to a multiple of this many positions, so
#: prompts of near lengths share one compiled reference
PAD_MULTIPLE = 64
ACT = {"swiglu": "silu", "gelu": "gelu"}


def model_config(conf: dict):
    """The program's ``ModelConfig``, checked against the sizes of the
    published decoder-only ``config.json`` the file states."""
    from repro.configs.registry import get_config
    cfg = get_config(conf["arch"])
    check(cfg)
    got = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
           "num_attention_heads": cfg.num_heads,
           "num_key_value_heads": cfg.num_kv_heads,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta,
           "tie_word_embeddings": cfg.tie_embeddings,
           "hidden_act": ACT[cfg.act]}
    if "head_dim" in conf["published"]:
        got["head_dim"] = cfg.head_dim
    return spec.published(conf, cfg, got)


def check(cfg) -> None:
    """Raise unless ``cfg`` is a decoder this reference computes."""
    if (cfg.family != "dense" or cfg.kv_quant != "none"
            or cfg.pos_embedding != "rope"):
        raise ValueError(
            f"{cfg.name}: decoder_lm computes the dense RoPE decoder with a "
            f"plain KV cache, not family {cfg.family!r}, kv_quant "
            f"{cfg.kv_quant!r}, positions {cfg.pos_embedding!r}")


# -- weights -----------------------------------------------------------------

def _tree(cfg, draw) -> dict:
    """The parameter tree of one dense LM; ``draw(shape, kind)`` gives
    each leaf. Kinds: ``w`` (a (out, in) matrix), ``bias``, ``scale`` (a
    norm gain), ``table`` (token embedding)."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    hd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    lead = (cfg.num_layers,)

    def lin(n_in, n_out, bias, lead):
        p = {"w": draw((*lead, n_out, n_in), "w")}
        if bias:
            p["b"] = draw((*lead, n_out), "bias")
        return p

    def norm(lead):
        p = {"scale": draw((*lead, d), "scale")}
        if cfg.norm == "layernorm":
            p["bias"] = draw((*lead, d), "bias")
        return p

    b = cfg.qkv_bias
    ffn = {"up": lin(d, f, False, lead), "down": lin(f, d, False, lead)}
    if cfg.act == "swiglu":
        ffn["gate"] = lin(d, f, False, lead)
    tree = {
        "embed": {"table": draw((v, d), "table")},
        "stack": {"blocks": [{
            "norm1": norm(lead),
            "attn": {"q": lin(d, hd, b, lead), "k": lin(d, kvd, b, lead),
                     "v": lin(d, kvd, b, lead), "o": lin(hd, d, False, lead)},
            "norm2": norm(lead), "ffn": ffn}]},
        "final_norm": norm(()),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = lin(d, v, False, ())
    return tree


def make_params(cfg, seed: int) -> dict:
    """The model's weights for ``seed``, on the default device, in
    ``cfg.param_dtype``, from one jitted call."""
    check(cfg)
    return weights.make(cfg, seed, _tree)


# -- requests ----------------------------------------------------------------

def payload(seed: int, index: int, size: int, cfg) -> np.ndarray:
    """Request ``index``'s prompt: ``size`` token ids drawn uniformly over
    the vocabulary from the seed and the index."""
    rng = np.random.default_rng([seed % 2**64, 2, index])
    return rng.integers(0, cfg.vocab_size, size).astype(np.int32)


def program_args(cfg, size: int) -> jax.ShapeDtypeStruct:
    """The prefill's input for a prompt of ``size`` tokens: each length
    is a program of its own."""
    return jax.ShapeDtypeStruct((1, size), jnp.int32)


def submit_kwargs(cfg) -> dict:
    return {}


def scheduler_kwargs(cfg) -> dict:
    return {}


# -- the float32 reference ---------------------------------------------------

def _norm(p, x, kind: str):
    """RMSNorm, or LayerNorm (centred first, then a bias added)."""
    if kind == "layernorm":
        x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + EPS)
    return x * p["scale"] + p["bias"] if kind == "layernorm" \
        else x * p["scale"]


def _rope(x, theta: float):
    """x (S, H, D): each head's halves rotated by position x frequency."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(a, h, heads: int, kv_heads: int, theta: float):
    """Causal grouped-query self-attention of h (S, d) -> (S, d)."""
    s = h.shape[0]
    q = _rope(linear(a["q"], h).reshape(s, heads, -1), theta)
    k = _rope(linear(a["k"], h).reshape(s, kv_heads, -1), theta)
    v = linear(a["v"], h).reshape(s, kv_heads, -1)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return linear(a["o"], jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1))


def _mlp(p, x, act: str):
    up = x @ p["up"]["w"].T
    h = (jax.nn.silu(x @ p["gate"]["w"].T) * up if act == "swiglu"
         else jax.nn.gelu(up))
    return h @ p["down"]["w"].T


def forward(p, tokens, cfg):
    """Teacher-forced LM: tokens (L,) i32 -> logits (L, padded vocab); row
    j is the next-token distribution after tokens[:j+1]."""
    x = p["embed"]["table"][tokens]
    blocks = p["stack"]["blocks"][0]
    for i in range(cfg.num_layers):
        b = layer(blocks, i)
        x = x + _attend(b["attn"], _norm(b["norm1"], x, cfg.norm),
                        cfg.num_heads, cfg.num_kv_heads, cfg.rope_theta)
        x = x + _mlp(b["ffn"], _norm(b["norm2"], x, cfg.norm), cfg.act)
    x = _norm(p["final_norm"], x, cfg.norm)
    head = p["embed"]["table"] if cfg.tie_embeddings else p["lm_head"]["w"]
    return x @ head.T


@functools.partial(jax.jit, static_argnames=("cfg",))
def logits(p, tokens, cfg):
    """Reference logits of one token sequence, in float32 at ``highest``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        return forward(p, tokens, cfg)


def reference_logits(params, cfg, payload, first, tokens, pad_to: int):
    """The reference's rows that predict each served token, and those
    tokens: ``first`` (the program's pick after the prompt) and then the
    tokens the decode steps emitted, teacher-forced after the prompt."""
    prompt = np.asarray(payload, np.int32).reshape(-1)
    served = np.asarray([first, *tokens], np.int32)
    n = len(prompt) + pad_to
    inp = np.zeros((-(-n // PAD_MULTIPLE) * PAD_MULTIPLE,), np.int32)
    inp[:len(prompt)] = prompt
    inp[len(prompt):len(prompt) + len(served) - 1] = served[:-1]
    rows = np.asarray(logits(params, inp, cfg))
    return rows[len(prompt) - 1:len(prompt) - 1 + len(served)], served


# -- model FLOPs -------------------------------------------------------------
# The FLOPs the forward needs, whatever runs it: a prefill is every prompt
# position through the layers (position j attending j + 1 positions) and
# one readout; the program's scan of one-token steps reads out every
# position, which is no model work. A decoded token's step attends every
# position before it and its own.

def _layers(cfg, ctx: int) -> int:
    """One position through every layer, attending ``ctx`` positions."""
    d = cfg.d_model
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    mats = 3 if cfg.act == "swiglu" else 2
    layer_ = (2 * d * (qd + 2 * kvd) + 2 * qd * d                # q, k, v, o
              + 2 * 2 * ctx * qd                                 # attention
              + 2 * mats * d * cfg.d_ff)                         # MLP
    return cfg.num_layers * layer_


def prefill_flops(cfg, size: int) -> int:
    """A prompt of ``size`` tokens, and the readout of its last."""
    return (sum(_layers(cfg, j) for j in range(1, size + 1))
            + 2 * cfg.d_model * cfg.vocab_size)


def token_flops(cfg, ctx: int, size: int) -> int:
    """The ``ctx``-th token the decode steps emit after a ``size``-token
    prompt: its step's input sits at position ``size + ctx - 1``."""
    return _layers(cfg, size + ctx) + 2 * cfg.d_model * cfg.vocab_size
