"""Whisper encoder-decoder backbone (the paper's workload, §3 Fig 1).

The conv frontend is a STUB per the assignment: ``input_specs()`` provides
precomputed 80-channel mel frames and a single linear projection stands in
for the two stride conv layers. Everything downstream — encoder self-attn
stack, decoder self+cross attention, tied vocab readout — is real and routes
every GEMM through the paper's offload engine when one is passed.

Decode follows whisper.cpp's split (paper Fig 1): the encoder runs once per
utterance, each decoder layer's cross K/V is projected once from the encoder
memory (``dec.cross.kv`` in the coverage enumeration), then tokens decode
autoregressively against the cached self-attention KV.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.attention import (
    KVCache, PagedKVCache, attention, decode_attention, init_attention)
from repro.models.transformer import _remat
from repro.sharding import ctx


class WhisperDecodeState(NamedTuple):
    self_kv: List[KVCache]          # stacked (R, ...) decoder self-attn cache
    cross_kv: Tuple[jax.Array, jax.Array]  # (R, B, F, Hkv, hd) x2, fixed

    # fields a decode step reads and never writes (model.step_writes)
    READ_ONLY = ("cross_kv",)


class WhisperPagedDecodeState(NamedTuple):
    """Paged slot-pool decode state (DESIGN.md §15.2): self-attn KV and
    the per-utterance cross-KV both live in fixed-shape page arenas, with
    one block table per slot shared by every layer (a page is ``page``
    positions x all ``R`` layers). Physical page 0 of each arena is the
    trash page free slots write/read through. ``length`` carries the
    per-layer (R, B) decode positions exactly like the contiguous slot
    layout, so ``decode_step`` position handling is unchanged."""
    self_k: jax.Array        # (R, P, page, Hkv, hd) self-KV page arena
    self_v: jax.Array        # (R, P, page, Hkv, hd)
    cross_k: jax.Array       # (R, Pc, cpage, Hkv, hd) cross-KV page arena
    cross_v: jax.Array       # (R, Pc, cpage, Hkv, hd)
    block_table: jax.Array   # (B, max_pages) i32 — self logical -> physical
    cross_table: jax.Array   # (B, n_cross_pages) i32 — frames -> physical
    length: jax.Array        # (R, B) i32 — tokens valid per layer/slot

    # fields a decode step reads and never writes (model.step_writes)
    READ_ONLY = ("cross_k", "cross_v", "block_table", "cross_table")


def warm_tuning(cfg: ModelConfig, engine, *, n_frames: int = 1500,
                n_tokens: int = 27, batch: int = 1,
                quant: Optional[str] = None) -> int:
    """Pre-tune every GEMM shape of one Whisper inference (the coverage
    enumerator's invocation classes, batch-scaled) so the first utterance
    never stalls on an autotuning sweep — the offline analog of the paper
    choosing its LMM/burst point before synthesis (DESIGN.md §9.4).
    ``quant`` is the *serving* quantization (ServeEngine may override
    cfg.quant); it selects which kernel family's keys get warmed. Returns
    the number of distinct shapes tuned; 0 if the engine carries no tuner."""
    if engine is None or getattr(engine, "tuner", None) is None:
        return 0
    from repro.core.coverage import MulMat, enumerate_whisper
    q = quant if quant is not None else cfg.quant
    dtype = "q8_0" if q == "q8_0" else "bf16"
    mulmats = [MulMat(m.name, m=m.m * batch, k=m.k, n=m.n)
               for m in enumerate_whisper(cfg, n_frames, n_tokens)]
    return engine.tuner.warm(mulmats, dtype=dtype)


def _stack_init(fn, key, r: int):
    return jax.vmap(fn)(jax.random.split(key, r))


def _init_enc_block(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "norm1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "norm2": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "ffn": layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def _init_dec_block(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "norm1": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "self_attn": init_attention(ks[0], cfg, dtype),
        "norm_x": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "cross_attn": init_attention(ks[1], cfg, dtype, cross=True),
        "norm2": layers.init_norm(cfg.d_model, cfg.norm, dtype),
        "ffn": layers.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def init_whisper(key, cfg: ModelConfig, max_positions: int = 0) -> dict:
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    maxp = max(max_positions, cfg.encoder_ctx, 448)
    return {
        # frontend stub: mel (.., n_mels) -> d_model (conv x2 stride 2 stand-in)
        "frontend": layers.init_linear(ks[0], cfg.n_mels, d, bias=True,
                                       dtype=dtype),
        "enc_pos": {"table": layers.sinusoidal_positions(maxp, d).astype(dtype)},
        "enc_blocks": _stack_init(lambda k: _init_enc_block(k, cfg, dtype),
                                  ks[1], cfg.num_encoder_layers),
        "enc_norm": layers.init_norm(d, cfg.norm, dtype),
        "embed": layers.init_embedding(ks[2], cfg.padded_vocab, d, dtype),
        "dec_pos": {"table": (jax.random.normal(ks[3], (maxp, d), jnp.float32)
                              * 0.01).astype(dtype)},
        "dec_blocks": _stack_init(lambda k: _init_dec_block(k, cfg, dtype),
                                  ks[4], cfg.num_layers),
        "dec_norm": layers.init_norm(d, cfg.norm, dtype),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------
def encode(params: dict, cfg: ModelConfig, mel: jax.Array, *,
           engine=None, attn_chunk: int = 2048) -> jax.Array:
    """mel: (B, F, n_mels) precomputed frames -> (B, F, d) memory.

    Trace-pure with an ``engine`` (DESIGN.md §10.1): serving jits the
    whole prefill (encode + cross-K/V projection) in one compiled call."""
    with jax.named_scope("encoder"):
        return _encode(params, cfg, mel, engine, attn_chunk)


def _encode(params: dict, cfg: ModelConfig, mel: jax.Array, engine,
            attn_chunk: int) -> jax.Array:
    with jax.named_scope("conv"):
        x = layers.linear(params["frontend"], mel.astype(jnp.float32),
                          engine, "enc.frontend")
        x = jax.nn.gelu(x)
        f = x.shape[1]
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
        x = (x + params["enc_pos"]["table"][:f].astype(jnp.float32)
             ).astype(dtype)

    def block(x, p):
        x = ctx.constrain(x, "batch", None, None)
        with jax.named_scope("self_attn"):
            h = layers.norm_apply(p["norm1"], x, cfg.norm)
            x = x + attention(p["attn"], cfg, h, causal=False,
                              chunk=attn_chunk, engine=engine).astype(x.dtype)
        with jax.named_scope("ffn"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
            x = x + layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine
                                     ).astype(x.dtype)
        return x

    block = _remat(block, cfg)
    if cfg.scan_layers:
        with layers.per_layer(engine, cfg.num_encoder_layers):
            x, _ = jax.lax.scan(lambda c, p: (block(c, p), None), x,
                                params["enc_blocks"])
    else:
        for i in range(cfg.num_encoder_layers):
            p = jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"])
            x = block(x, p)
    return layers.norm_apply(params["enc_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# Decoder (teacher-forced full sequence)
# ---------------------------------------------------------------------------
def decode_train(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 memory: jax.Array, *, engine=None,
                 attn_chunk: int = 2048,
                 return_hidden: bool = False) -> jax.Array:
    """tokens: (B, T) -> logits (B, T, V), attending to encoder memory.
    return_hidden skips final norm + readout (chunked-CE path)."""
    t = tokens.shape[1]
    x = layers.embed(params["embed"], tokens)
    x = x + params["dec_pos"]["table"][:t].astype(x.dtype)

    def block(x, p):
        x = ctx.constrain(x, "batch", None, None)
        with jax.named_scope("self_attn"):
            h = layers.norm_apply(p["norm1"], x, cfg.norm)
            x = x + attention(p["self_attn"], cfg, h, causal=True,
                              chunk=attn_chunk, engine=engine).astype(x.dtype)
        with jax.named_scope("cross_attn"):
            h = layers.norm_apply(p["norm_x"], x, cfg.norm)
            x = x + attention(p["cross_attn"], cfg, h, memory=memory,
                              chunk=attn_chunk, engine=engine).astype(x.dtype)
        with jax.named_scope("ffn"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
            x = x + layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine
                                     ).astype(x.dtype)
        return x

    block = _remat(block, cfg)
    with jax.named_scope("decoder"):
        if cfg.scan_layers:
            with layers.per_layer(engine, cfg.num_layers):
                x, _ = jax.lax.scan(lambda c, p: (block(c, p), None), x,
                                    params["dec_blocks"])
        else:
            for i in range(cfg.num_layers):
                p = jax.tree_util.tree_map(lambda a: a[i],
                                           params["dec_blocks"])
                x = block(x, p)
    if return_hidden:
        return x
    return readout(params, cfg, x, engine)


def readout(params: dict, cfg: ModelConfig, x: jax.Array,
            engine=None) -> jax.Array:
    """Final norm and the tied vocabulary readout."""
    with jax.named_scope("readout"):
        x = layers.norm_apply(params["dec_norm"], x, cfg.norm)
        return layers.unembed(params["embed"], x, engine)


# ---------------------------------------------------------------------------
# Autoregressive decode
# ---------------------------------------------------------------------------
def precompute_cross_kv(params: dict, cfg: ModelConfig, memory: jax.Array, *,
                        engine=None) -> Tuple[jax.Array, jax.Array]:
    """Project each decoder layer's cross K/V once per utterance
    (the paper's ``dec.cross.kv`` kernel class). Returns (R,B,F,Hkv,hd) x2."""
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    b, f, _ = memory.shape

    def per_layer(p):
        k = layers.linear(p["cross_attn"]["k"], memory, engine, "dec.cross.k")
        v = layers.linear(p["cross_attn"]["v"], memory, engine, "dec.cross.v")
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
        return (k.reshape(b, f, hkv, hd).astype(dtype),
                v.reshape(b, f, hkv, hd).astype(dtype))

    with jax.named_scope("cross_kv"), layers.per_layer(engine,
                                                       cfg.num_layers):
        return jax.vmap(per_layer)(params["dec_blocks"])


def init_whisper_decode_state(params: dict, cfg: ModelConfig, memory: jax.Array,
                              max_len: int, *, engine=None,
                              dtype=jnp.bfloat16) -> WhisperDecodeState:
    b = memory.shape[0]
    kv = KVCache.zeros(b, max_len, cfg.num_kv_heads, cfg.head_dim, dtype)
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)), kv)
    return WhisperDecodeState(
        self_kv=stacked,
        cross_kv=precompute_cross_kv(params, cfg, memory, engine=engine))


def _paged_stack(params: dict, cfg: ModelConfig, x: jax.Array,
                 state: WhisperPagedDecodeState, *, engine=None
                 ) -> Tuple[jax.Array, WhisperPagedDecodeState]:
    """Shared paged decoder-block stack (DESIGN.md §15.2/§17.4) over a
    (B, W, d) embedded+positioned window: self-KV reads/writes go through
    the per-slot block table (see ``attention.PagedKVCache`` — W > 1
    scatters every window entry through its own (page, offset) pair) and
    each layer's cross-KV is gathered from its pages back into the
    contiguous (B, F, Hkv, hd) view — F is an exact multiple of the cross
    page size (pool invariant), so position t of the gathered view IS
    position t of the contiguous one and the attention math (hence every
    token) is unchanged."""
    b = x.shape[0]
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    bt, ct = state.block_table, state.cross_table

    def body(x, xs):
        p, sk, sv, length, ckp, cvp = xs
        cache = PagedKVCache(sk, sv, bt, length)
        with jax.named_scope("self_attn"):
            h = layers.norm_apply(p["norm1"], x, cfg.norm)
            mixed, cache = decode_attention(p["self_attn"], cfg, h, cache,
                                            engine=engine)
            x = x + mixed.astype(x.dtype)
        with jax.named_scope("cross_attn"):
            ck = ckp[ct].reshape(b, -1, hkv, hd)
            cv = cvp[ct].reshape(b, -1, hkv, hd)
            h = layers.norm_apply(p["norm_x"], x, cfg.norm)
            mixed, _ = decode_attention(p["cross_attn"], cfg, h, cache,
                                        memory_kv=(ck, cv), engine=engine)
            x = x + mixed.astype(x.dtype)
        with jax.named_scope("ffn"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
            x = x + layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine
                                     ).astype(x.dtype)
        return x, (cache.k_pages, cache.v_pages, cache.length)

    xs = (params["dec_blocks"], state.self_k, state.self_v, state.length,
          state.cross_k, state.cross_v)
    with jax.named_scope("decoder"):
        if cfg.scan_layers:
            with layers.per_layer(engine, cfg.num_layers):
                x, (nk, nv, nl) = jax.lax.scan(body, x, xs)
        else:
            outs = []
            for i in range(cfg.num_layers):
                xi = jax.tree_util.tree_map(lambda a: a[i], xs)
                x, o = body(x, xi)
                outs.append(o)
            nk, nv, nl = (jnp.stack([o[j] for o in outs]) for j in range(3))
    logits = readout(params, cfg, x, engine)
    return logits, WhisperPagedDecodeState(
        self_k=nk, self_v=nv, cross_k=state.cross_k, cross_v=state.cross_v,
        block_table=bt, cross_table=ct, length=nl)


def _decode_step_paged(params: dict, cfg: ModelConfig, token: jax.Array,
                       state: WhisperPagedDecodeState, *, engine=None
                       ) -> Tuple[jax.Array, WhisperPagedDecodeState]:
    """Paged twin of ``decode_step``: embed + per-slot position, then the
    shared paged stack at W=1."""
    x = layers.embed(params["embed"], token)
    pos = state.length[0]                       # (B,) per-slot positions
    table = params["dec_pos"]["table"]
    x = x + jnp.take(table, pos, axis=0)[:, None].astype(x.dtype)
    return _paged_stack(params, cfg, x, state, engine=engine)


def _verify_step_paged(params: dict, cfg: ModelConfig, tokens: jax.Array,
                      state: WhisperPagedDecodeState, *, engine=None
                      ) -> Tuple[jax.Array, WhisperPagedDecodeState]:
    """Paged twin of ``verify_step`` (DESIGN.md §17.4): the W-token
    verify window scores in ONE forward through the shared paged stack —
    window position j reads its learned positional row at ``length[b] +
    j`` and its self-KV entry scatters through the block table, so the
    logits match the contiguous verify bit-for-bit."""
    w = tokens.shape[1]
    x = layers.embed(params["embed"], tokens)
    pos = state.length[0]                       # (B,) per-slot positions
    table = params["dec_pos"]["table"]
    posw = pos[:, None] + jnp.arange(w)[None, :]
    x = x + jnp.take(table, posw, axis=0).astype(x.dtype)
    return _paged_stack(params, cfg, x, state, engine=engine)


def _decoder_stack(params: dict, cfg: ModelConfig, x: jax.Array,
                   state: WhisperDecodeState, *, engine=None
                   ) -> Tuple[jax.Array, WhisperDecodeState]:
    """Shared decoder-block stack for the one-token step and the W-token
    verify window (DESIGN.md §17.1): x is (B, W, d) embedded+positioned
    input; ``decode_attention`` appends all W self-KV entries and masks
    window causality, so W=1 reproduces the old step bit-for-bit."""
    def body(x, xs):
        p, kv, ck, cv = xs
        with jax.named_scope("self_attn"):
            h = layers.norm_apply(p["norm1"], x, cfg.norm)
            mixed, kv = decode_attention(p["self_attn"], cfg, h, kv,
                                         engine=engine)
            x = x + mixed.astype(x.dtype)
        with jax.named_scope("cross_attn"):
            h = layers.norm_apply(p["norm_x"], x, cfg.norm)
            mixed, _ = decode_attention(p["cross_attn"], cfg, h, kv,
                                        memory_kv=(ck, cv), engine=engine)
            x = x + mixed.astype(x.dtype)
        with jax.named_scope("ffn"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
            x = x + layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine
                                     ).astype(x.dtype)
        return x, kv

    ck, cv = state.cross_kv
    with jax.named_scope("decoder"):
        if cfg.scan_layers:
            with layers.per_layer(engine, cfg.num_layers):
                x, new_kv = jax.lax.scan(body, x, (params["dec_blocks"],
                                                   state.self_kv, ck, cv))
        else:
            caches = []
            for i in range(cfg.num_layers):
                xs = jax.tree_util.tree_map(
                    lambda a: a[i],
                    (params["dec_blocks"], state.self_kv, ck, cv))
                x, kv_i = body(x, xs)
                caches.append(kv_i)
            new_kv = jax.tree_util.tree_map(lambda *z: jnp.stack(z),
                                            *caches)
    logits = readout(params, cfg, x, engine)
    return logits, WhisperDecodeState(self_kv=new_kv, cross_kv=state.cross_kv)


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                state: WhisperDecodeState, *, engine=None
                ) -> Tuple[jax.Array, WhisperDecodeState]:
    """token: (B, 1) int32 -> (logits (B, 1, V), state').

    Positions come from the layer-0 self-KV length: scalar for a lockstep
    batch, per-row ``(B,)`` in the slot-pool layout (DESIGN.md §11.1) —
    each slot then reads its own learned positional embedding row.
    ``WhisperPagedDecodeState`` dispatches to the paged twin
    (DESIGN.md §15.2)."""
    if isinstance(state, WhisperPagedDecodeState):
        return _decode_step_paged(params, cfg, token, state, engine=engine)
    x = layers.embed(params["embed"], token)
    pos = (state.self_kv.length[0] if state.self_kv.length.ndim
           else state.self_kv.length)
    table = params["dec_pos"]["table"]
    if pos.ndim:                                    # per-slot positions (B,)
        x = x + jnp.take(table, pos, axis=0)[:, None].astype(x.dtype)
    else:
        x = x + jax.lax.dynamic_slice_in_dim(table, pos, 1,
                                             axis=0).astype(x.dtype)
    return _decoder_stack(params, cfg, x, state, engine=engine)


def verify_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                state: WhisperDecodeState, *, engine=None
                ) -> Tuple[jax.Array, WhisperDecodeState]:
    """Score a W-token window in ONE forward (DESIGN.md §17.1): tokens
    (B, W) int32 -> (logits (B, W, V), state') with every layer's self-KV
    advanced by W. ``logits[:, j]`` is the next-token distribution after
    consuming ``tokens[:, :j+1]`` — exactly what ``decode_step`` would
    return fed those tokens one at a time, which is what makes
    speculative acceptance token-exact against the greedy verifier.
    Position handling mirrors ``decode_step``: the layer-0 self-KV length
    is the window base, scalar (lockstep) or per-row (slot layout)."""
    if isinstance(state, WhisperPagedDecodeState):
        return _verify_step_paged(params, cfg, tokens, state, engine=engine)
    w = tokens.shape[1]
    x = layers.embed(params["embed"], tokens)
    pos = (state.self_kv.length[0] if state.self_kv.length.ndim
           else state.self_kv.length)
    table = params["dec_pos"]["table"]
    if pos.ndim:                                    # per-slot positions (B,)
        posw = pos[:, None] + jnp.arange(w)[None, :]
        x = x + jnp.take(table, posw, axis=0).astype(x.dtype)
    else:
        x = x + jax.lax.dynamic_slice_in_dim(table, pos, w,
                                             axis=0)[None].astype(x.dtype)
    return _decoder_stack(params, cfg, x, state, engine=engine)
