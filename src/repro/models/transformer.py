"""Decoder-only transformer stack for the dense / MoE / SSM / hybrid / VLM
families, with a periodic layer *pattern* so heterogeneous stacks (jamba's
1:7 attention:mamba interleave with MoE every other layer) still scan.

Layers are grouped into a repeating pattern of length P (P = lcm of the
attention and MoE periods); parameters are stacked (R, ...) per pattern
position with R = num_layers / P repeats. ``lax.scan`` over R keeps the HLO
(and compile time) O(P) instead of O(num_layers) — essential for the
80-layer qwen1.5-110b dry-run — and ``jax.checkpoint`` applies the remat
policy per scanned block.
"""
from __future__ import annotations

import math
from typing import List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, moe as moe_lib, ssm as ssm_lib
from repro.models.attention import KVCache, attention, decode_attention, init_attention
from repro.sharding import ctx


class LayerSpec(NamedTuple):
    mixer: str   # "attn" | "ssm"
    ffn: str     # "dense" | "moe" | "none"


def layer_pattern(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    p = 1
    if cfg.family == "hybrid":
        p = math.lcm(cfg.attn_every, cfg.moe_every if cfg.moe else 1)
    elif cfg.moe is not None and cfg.moe_every > 1:
        p = cfg.moe_every
    if cfg.num_layers % p:
        raise ValueError(f"{cfg.name}: num_layers {cfg.num_layers} "
                         f"not divisible by pattern {p}")
    specs = []
    for i in range(p):
        if cfg.family == "ssm":
            mixer = "ssm"
        elif cfg.family == "hybrid":
            mixer = "attn" if i % cfg.attn_every == cfg.attn_offset else "ssm"
        else:
            mixer = "attn"
        if cfg.moe is not None and i % cfg.moe_every == cfg.moe_offset:
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "dense"
        else:
            ffn = "none"
        specs.append(LayerSpec(mixer, ffn))
    return tuple(specs)


def n_repeats(cfg: ModelConfig) -> int:
    return cfg.num_layers // len(layer_pattern(cfg))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _stack_init(fn, key, r: int):
    """vmap an init over R repeats -> leaves gain a leading (R, ...) dim."""
    return jax.vmap(fn)(jax.random.split(key, r))


def _init_block(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {"norm1": layers.init_norm(cfg.d_model, cfg.norm, dtype)}
    if spec.mixer == "attn":
        p["attn"] = init_attention(ks[0], cfg, dtype)
    else:
        p["ssm"] = ssm_lib.init_ssm(ks[0], cfg, dtype)
    if spec.ffn != "none":
        p["norm2"] = layers.init_norm(cfg.d_model, cfg.norm, dtype)
        if spec.ffn == "moe":
            p["moe"] = moe_lib.init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def init_decoder_stack(key, cfg: ModelConfig) -> dict:
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]
    pattern = layer_pattern(cfg)
    r = n_repeats(cfg)
    ks = jax.random.split(key, len(pattern))
    blocks = [
        _stack_init(lambda k, s=spec: _init_block(k, cfg, s, dtype), ks[i], r)
        for i, spec in enumerate(pattern)
    ]
    return {"blocks": blocks}


# ---------------------------------------------------------------------------
# Full-sequence apply (train / prefill)
# ---------------------------------------------------------------------------
def _apply_block(p: dict, cfg: ModelConfig, spec: LayerSpec, x: jax.Array, *,
                 positions, engine, attn_chunk: int) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    # batch pinned only; residual-stream sequence parallelism measured
    # WORSE here (collective +75%, §Perf A5 refuted — GSPMD inserts extra
    # resharding at the MoE/router and CE boundaries instead of clean
    # all-gather/reduce-scatter pairs)
    x = ctx.constrain(x, "batch", None, None)
    h = layers.norm_apply(p["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        mixed = attention(p["attn"], cfg, h, positions=positions,
                          causal=True, chunk=attn_chunk, engine=engine)
    else:
        mixed = ssm_lib.ssm_mixer(p["ssm"], cfg, h, engine=engine)
    x = x + mixed.astype(x.dtype)
    if spec.ffn != "none":
        h = layers.norm_apply(p["norm2"], x, cfg.norm)
        if spec.ffn == "moe":
            y, aux = moe_lib.moe_ffn(p["moe"], cfg, h, engine=engine)
        else:
            y = layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine)
        x = x + y.astype(x.dtype)
    return x, aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def apply_decoder_stack(params: dict, cfg: ModelConfig, x: jax.Array, *,
                        positions: Optional[jax.Array] = None,
                        engine=None, attn_chunk: int = 2048
                        ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, moe_aux_loss)."""
    pattern = layer_pattern(cfg)

    def repeat_fn(x, block_params: List[dict]):
        aux = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(pattern):
            x, a = _apply_block(block_params[i], cfg, spec, x,
                                positions=positions, engine=engine,
                                attn_chunk=attn_chunk)
            aux = aux + a
        return x, aux

    repeat_fn = _remat(repeat_fn, cfg)

    if cfg.scan_layers:
        def body(carry, xs):
            x, aux = carry
            x, a = repeat_fn(x, xs)
            return (x, aux + a), None
        with layers.per_layer(engine, n_repeats(cfg)):
            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        for r in range(n_repeats(cfg)):
            block_r = jax.tree_util.tree_map(lambda a: a[r], params["blocks"])
            x, a = repeat_fn(x, block_r)
            aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# Decode (one token, carried state)
# ---------------------------------------------------------------------------
LayerState = Union[KVCache, ssm_lib.SSMState]


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> List[LayerState]:
    """Per-pattern-position stacked states (leading dim R)."""
    pattern = layer_pattern(cfg)
    r = n_repeats(cfg)
    out: List[LayerState] = []
    for spec in pattern:
        if spec.mixer == "attn":
            cache_cls = (__import__("repro.models.attention",
                                    fromlist=["QKVCache"]).QKVCache
                         if cfg.kv_quant == "q8" else KVCache)
            st = cache_cls.zeros(batch, max_len, cfg.num_kv_heads,
                                 cfg.head_dim, dtype)
        else:
            st = ssm_lib.SSMState.zeros(batch, cfg.ssm, cfg.d_model)
        out.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (r, *a.shape)), st))
    return out


def decode_step_stack(params: dict, cfg: ModelConfig, x: jax.Array,
                      states: List[LayerState], *, engine=None
                      ) -> Tuple[jax.Array, List[LayerState]]:
    """x: (B, 1, d); states as from init_decode_state. Returns (y, states')."""
    pattern = layer_pattern(cfg)

    def repeat_fn(x, block_params, states_r):
        new_states = []
        for i, spec in enumerate(pattern):
            p = block_params[i]
            h = layers.norm_apply(p["norm1"], x, cfg.norm)
            if spec.mixer == "attn":
                mixed, st = decode_attention(p["attn"], cfg, h, states_r[i],
                                             engine=engine)
            else:
                mixed, st = ssm_lib.ssm_decode_step(p["ssm"], cfg, h,
                                                    states_r[i], engine=engine)
            x = x + mixed.astype(x.dtype)
            new_states.append(st)
            if spec.ffn != "none":
                h = layers.norm_apply(p["norm2"], x, cfg.norm)
                if spec.ffn == "moe":
                    y, _ = moe_lib.moe_ffn(p["moe"], cfg, h, engine=engine)
                else:
                    y = layers.mlp_apply(p["ffn"], h, cfg.act, engine=engine)
                x = x + y.astype(x.dtype)
        return x, new_states

    if cfg.scan_layers:
        def body(x, xs):
            block_params, states_r = xs
            x, new_states = repeat_fn(x, block_params, states_r)
            return x, new_states
        with layers.per_layer(engine, n_repeats(cfg)):
            x, new_states = jax.lax.scan(body, x, (params["blocks"], states))
    else:
        r = n_repeats(cfg)
        acc = []
        for i in range(r):
            block_r = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            states_r = jax.tree_util.tree_map(lambda a: a[i], states)
            x, st = repeat_fn(x, block_r, states_r)
            acc.append(st)
        # restack (R, ...) per position
        new_states = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *acc)
    return x, new_states
