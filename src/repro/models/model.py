"""Family dispatch: one public API over every assigned architecture.

  init_params(key, cfg, max_positions)        -> param pytree
  forward(params, cfg, batch)                 -> (logits, aux)   train/prefill
  loss_fn(params, cfg, batch)                 -> (loss, metrics)
  init_serve_state(params, cfg, batch, max_len) -> decode state pytree
  serve_step(params, cfg, token, state)       -> (logits, state')  one token

Batch dict conventions (mirrored by launch/input_specs.py):
  LM families : {"tokens": (B,S) i32, "labels": (B,S) i32}
  vlm         : + {"patches": (B,P,E_vis) f32}  — precomputed anyres tiles,
                projected and spliced over the first P token positions
  audio       : {"mel": (B,F,n_mels) f32, "tokens": (B,T), "labels": (B,T)}
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, transformer, whisper
from repro.sharding import ctx


class ServeState(NamedTuple):
    """Decode-state wrapper uniform across families.

    Two layouts share this type (DESIGN.md §11.1):
      standard — ``step`` and the per-layer cache ``length`` counters are
        scalars; every batch row decodes in lockstep (generate/transcribe).
      slot     — counters are per-slot vectors (``step``: (B,), stacked
        lengths: (R, B)) so each slot of a continuous-batching pool sits
        at its own position inside one fixed-shape batch.
    ``slot_layout`` converts standard -> slot; data leaves are identical
    in both (counters aside, every layer_states leaf carries the batch on
    axis 1, after the layer-stack axis — the invariant the slot-pool
    splice in serve/kvcache.py relies on).
    """
    layer_states: Any     # list per pattern position (LM) | WhisperDecodeState
    step: jax.Array       # () or (B,) i32 — absolute position of next token


def step_writes(state: ServeState) -> ServeState:
    """The part of ``state`` a decode program writes and returns
    (DESIGN.md §11.2). The per-utterance cross-KV (contiguous ``cross_kv``,
    or the paged cross arenas and both page tables) is projected at
    admission and only read while a request decodes; a program that
    returned it would have XLA copy it whole into fresh output buffers on
    every call, so those leaves come back as ``None``. LM states have no
    read-only leaves and return whole."""
    ls = state.layer_states
    if isinstance(ls, whisper.WhisperDecodeState):
        ls = ls._replace(cross_kv=None)
    elif isinstance(ls, whisper.WhisperPagedDecodeState):
        ls = ls._replace(cross_k=None, cross_v=None, block_table=None,
                         cross_table=None)
    return ServeState(layer_states=ls, step=state.step)


def with_step_writes(state: ServeState, written: ServeState) -> ServeState:
    """``state`` with the leaves a decode program wrote taken from
    ``written``; the read-only leaves stay ``state``'s own arrays, by
    reference. ``written`` may also be a full state: only its written
    leaves are read."""
    ls, w = state.layer_states, written.layer_states
    if isinstance(ls, whisper.WhisperDecodeState):
        ls = ls._replace(self_kv=w.self_kv)
    elif isinstance(ls, whisper.WhisperPagedDecodeState):
        ls = ls._replace(self_k=w.self_k, self_v=w.self_v, length=w.length)
    else:
        ls = w
    return ServeState(layer_states=ls, step=written.step)


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, max_positions: int = 0) -> dict:
    if cfg.family == "audio":
        return whisper.init_whisper(key, cfg, max_positions)
    pdtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]
    ks = jax.random.split(key, 4)
    params = {
        "embed": layers.init_embedding(ks[0], cfg.padded_vocab, cfg.d_model,
                                       pdtype),
        "stack": transformer.init_decoder_stack(ks[1], cfg),
        "final_norm": layers.init_norm(cfg.d_model, cfg.norm, pdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_linear(ks[2], cfg.d_model,
                                               cfg.padded_vocab, dtype=pdtype)
    if cfg.family == "vlm":
        params["projector"] = layers.init_linear(
            ks[3], cfg.vision_embed_dim, cfg.d_model, bias=True, dtype=pdtype)
    return params


# ---------------------------------------------------------------------------
# Embedding / readout shared by LM families
# ---------------------------------------------------------------------------
def _embed_inputs(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  engine=None) -> jax.Array:
    x = layers.embed(params["embed"], batch["tokens"]).astype(_dtype(cfg))
    if cfg.family == "vlm" and "patches" in batch:
        proj = layers.linear(params["projector"], batch["patches"],
                             engine, "vlm.projector").astype(x.dtype)
        p = proj.shape[1]
        # splice: precomputed patch embeddings occupy the first P positions
        x = jnp.concatenate([proj, x[:, p:]], axis=1)
    return ctx.constrain(x, "batch", None, None)


def _readout(params: dict, cfg: ModelConfig, x: jax.Array,
             engine=None) -> jax.Array:
    x = layers.norm_apply(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], x, engine)
    return layers.linear(params["lm_head"], x, engine, "lm_head")


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def hidden_forward(params: dict, cfg: ModelConfig,
                   batch: Dict[str, jax.Array], *,
                   engine=None, attn_chunk: int = 2048
                   ) -> Tuple[jax.Array, jax.Array]:
    """Backbone only: (final hidden states pre-readout, moe_aux_loss)."""
    if cfg.family == "audio":
        memory = whisper.encode(params, cfg, batch["mel"], engine=engine,
                                attn_chunk=attn_chunk)
        h = whisper.decode_train(params, cfg, batch["tokens"], memory,
                                 engine=engine, attn_chunk=attn_chunk,
                                 return_hidden=True)
        return h, jnp.zeros((), jnp.float32)
    x = _embed_inputs(params, cfg, batch, engine)
    positions = jnp.arange(x.shape[1])[None, :]
    x, aux = transformer.apply_decoder_stack(params["stack"], cfg, x,
                                             positions=positions,
                                             engine=engine,
                                             attn_chunk=attn_chunk)
    return x, aux


def forward(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            engine=None, attn_chunk: int = 2048
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits, moe_aux_loss)."""
    h, aux = hidden_forward(params, cfg, batch, engine=engine,
                            attn_chunk=attn_chunk)
    if cfg.family == "audio":
        return whisper_readout(params, cfg, h, engine), aux
    return _readout(params, cfg, h, engine), aux


def whisper_readout(params: dict, cfg: ModelConfig, x: jax.Array,
                    engine=None) -> jax.Array:
    return whisper.readout(params, cfg, x, engine)


def _ce_of_logits(logits: jax.Array, labels: jax.Array,
                  vocab_size: int) -> Tuple[jax.Array, jax.Array]:
    """Masked CE sums for one chunk. Pad columns (>= vocab_size) excluded."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    if v > vocab_size:  # Megatron-style vocab pad: mask pad columns
        col = jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
        logits = jnp.where(col < vocab_size, logits, -1e30)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def loss_fn(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            engine=None, attn_chunk: int = 2048, ce_chunk: int = 512
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (labels already shifted by the data pipeline);
    label -1 positions are masked.

    The readout + CE is *sequence-chunked* under jax.checkpoint: full
    (B, S, V) f32 logits are never materialized — at qwen/whisper scale
    (V=152k/52k, B*S=1M tokens) the monolithic logits tensor alone would be
    hundreds of GiB per pod. Chunking costs one extra readout GEMM in the
    backward pass per chunk (remat) and bounds the logits temp at
    (B, ce_chunk, V).
    """
    h, aux = hidden_forward(params, cfg, batch, engine=engine,
                            attn_chunk=attn_chunk)
    labels = batch["labels"]
    readout = (whisper_readout if cfg.family == "audio" else _readout)

    b, s, d = h.shape
    n_chunks = s // ce_chunk if (s % ce_chunk == 0 and s > ce_chunk) else 1
    if n_chunks == 1:
        logits = readout(params, cfg, h, engine)
        ce_sum, ntok = _ce_of_logits(logits, labels, cfg.vocab_size)
    else:
        hc = jnp.moveaxis(h.reshape(b, n_chunks, ce_chunk, d), 1, 0)
        lc = jnp.moveaxis(labels.reshape(b, n_chunks, ce_chunk), 1, 0)

        @jax.checkpoint
        def chunk_ce(h_i, l_i):
            logits = readout(params, cfg, h_i, engine)
            logits = ctx.constrain(logits, "batch", None, "model")
            return _ce_of_logits(logits, l_i, cfg.vocab_size)

        def body(carry, xs):
            cs, nt = chunk_ce(*xs)
            return (carry[0] + cs, carry[1] + nt), None

        (ce_sum, ntok), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, lc))
    ntok = jnp.maximum(ntok, 1.0)
    loss = ce_sum / ntok
    total = loss + aux
    return total, {"ce": loss, "moe_aux": aux, "ntok": ntok}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def slot_layout(state: ServeState, batch: int) -> ServeState:
    """Standard -> slot layout (DESIGN.md §11.1): broadcast the scalar
    step/length counters to per-slot vectors so each row of a fixed-shape
    slot pool tracks its own decode position.

    The leaf rule is structural: counters are the only ``ndim <= 1``
    leaves of a decode state — ``()`` (an unstacked length / ServeState
    ``step``) broadcasts to ``(batch,)``, ``(R,)`` (a layer-stacked
    length) to ``(R, batch)``. Every data leaf (KV buffers, SSM states,
    whisper cross-KV) is ``ndim >= 3`` with the batch on axis 1 and
    passes through untouched. Idempotent on already-slot-layout states.
    """
    def conv(a):
        if a.ndim == 0:
            return jnp.broadcast_to(a, (batch,))
        if a.ndim == 1:
            return jnp.broadcast_to(a[:, None], (a.shape[0], batch))
        return a

    step = (jnp.broadcast_to(state.step, (batch,)) if state.step.ndim == 0
            else state.step)
    return ServeState(
        layer_states=jax.tree_util.tree_map(conv, state.layer_states),
        step=step)


def slot_batch_axis(leaf_is_step: bool) -> int:
    """Batch axis of a slot-layout leaf for the pool splice
    (serve/kvcache.py): ``ServeState.step`` is ``(B,)`` -> axis 0; every
    ``layer_states`` leaf — data and ``(R, B)`` counters alike — carries
    the batch on axis 1 after the layer-stack axis."""
    return 0 if leaf_is_step else 1


def slot_state_specs(state: ServeState, mesh) -> ServeState:
    """PartitionSpec pytree for a slot-layout ``ServeState``: the slot
    axis (``slot_batch_axis``) shards over the mesh's "data" axis — the
    slot pool IS sharded serving's data axis (DESIGN.md §13) — whenever
    the pool width divides it; every other dim stays replicated. Lives
    next to ``slot_layout`` because it encodes the same structural
    invariant (batch on axis 1 of every ``layer_states`` leaf); the
    divisibility fallback keeps one call site valid on any mesh, in the
    style of sharding/rules.py."""
    from jax.sharding import PartitionSpec as P
    dsize = mesh.shape["data"] if "data" in mesh.axis_names else 1

    def spec(leaf, axis):
        if dsize <= 1 or leaf.ndim <= axis or leaf.shape[axis] % dsize:
            return P()
        return P(*([None] * axis + ["data"]))

    return ServeState(
        layer_states=jax.tree_util.tree_map(
            lambda l: spec(l, slot_batch_axis(False)), state.layer_states),
        step=spec(state.step, slot_batch_axis(True)))


def state_kv_bytes(state: Any) -> int:
    """Committed bytes of a decode-state pytree (KV buffers + counters +
    block tables). The serving benchmarks report this next to tok/s so
    the paged pool's memory win (DESIGN.md §15.4) is measured by the same
    harness that gates token parity."""
    return sum(int(l.size) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(state))


def init_serve_state(params: dict, cfg: ModelConfig, batch: int, max_len: int,
                     *, memory: Optional[jax.Array] = None, engine=None,
                     prefill_len: int = 0) -> ServeState:
    if cfg.family == "audio":
        assert memory is not None, "whisper decode needs encoder memory"
        st = whisper.init_whisper_decode_state(params, cfg, memory, max_len,
                                               engine=engine, dtype=_dtype(cfg))
    else:
        st = transformer.init_decode_state(cfg, batch, max_len, _dtype(cfg))
    return ServeState(layer_states=st, step=jnp.asarray(prefill_len, jnp.int32))


def serve_step(params: dict, cfg: ModelConfig, token: jax.Array,
               state: ServeState, *, engine=None
               ) -> Tuple[jax.Array, ServeState]:
    """token: (B, 1) i32 -> (logits (B, 1, V), state').

    Trace-pure with an ``engine`` attached (DESIGN.md §10.1): offload
    routing resolves from static shapes at trace time and nothing mutates
    host state, so serve/engine.py jits this step unconditionally
    (regression-tested by tests/test_plan.py)."""
    if cfg.family == "audio":
        logits, st = whisper.decode_step(params, cfg, token,
                                         state.layer_states, engine=engine)
        return logits, ServeState(st, state.step + 1)
    x = layers.embed(params["embed"], token).astype(_dtype(cfg))
    x, st = transformer.decode_step_stack(params["stack"], cfg, x,
                                          state.layer_states, engine=engine)
    logits = _readout(params, cfg, x, engine)
    return logits, ServeState(st, state.step + 1)


def verify_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                state: ServeState, *, engine=None
                ) -> Tuple[jax.Array, ServeState]:
    """Score a W-token verify window in one forward (DESIGN.md §17.1):
    tokens (B, W) i32 -> (logits (B, W, V), state') with every cache
    length (and ``step``) advanced by W. ``logits[:, j]`` equals what
    ``serve_step`` would emit after feeding ``tokens[:, :j+1]`` one at a
    time — the token-exactness contract speculative acceptance relies
    on. Audio (whisper) only for now: the draft/verify ladder is the
    Whisper scaling study's regime (tiny drafts, base/small verifies)."""
    if cfg.family != "audio":
        raise NotImplementedError(
            "speculative verify windows are wired for the audio family "
            "(the Whisper ladder); LM families still serve_step one token")
    logits, st = whisper.verify_step(params, cfg, tokens,
                                     state.layer_states, engine=engine)
    return logits, ServeState(st, state.step + tokens.shape[1])


def set_slot_lengths(state: ServeState, new_len: jax.Array) -> ServeState:
    """Splice per-slot decode positions to ``new_len`` (B,) — the
    speculative rollback (DESIGN.md §17.1): after a verify window
    advanced every length by W, the accepted prefix keeps only
    ``1 + accept_len`` of those tokens, so the counters rewind while the
    over-written KV entries beyond ``new_len`` stay in place (masked by
    the validity test, then overwritten by the next window).

    Structural rule, the inverse discipline of ``slot_layout``: in the
    slot layout the counters are exactly the ``ndim <= 2`` leaves —
    ``step`` (B,) and layer-stacked lengths (R, B) — and every data leaf
    is ``ndim >= 3``, so counters broadcast-assign from ``new_len`` and
    data passes through untouched.

    The paged layout (DESIGN.md §15.2) breaks that structural rule: its
    block/cross tables are ndim-2 *data* leaves (B, max_pages) int32, so
    it splices by field name instead — only ``length`` (R, B) and
    ``step`` rewind; the tables and page arenas pass through untouched
    (rejected-suffix *pages* are released host-side by the paged
    scheduler's post-round trim, DESIGN.md §17.4)."""
    new_len = jnp.asarray(new_len, jnp.int32)
    ls = state.layer_states
    if isinstance(ls, whisper.WhisperPagedDecodeState):
        ls = ls._replace(
            length=jnp.broadcast_to(new_len[None, :], ls.length.shape))
        return ServeState(layer_states=ls,
                          step=jnp.broadcast_to(new_len, state.step.shape))

    def conv(a):
        if a.ndim == 1:                       # (B,) unstacked counter
            return jnp.broadcast_to(new_len, a.shape)
        if a.ndim == 2:                       # (R, B) layer-stacked counter
            return jnp.broadcast_to(new_len[None, :], a.shape)
        return a

    return ServeState(
        layer_states=jax.tree_util.tree_map(conv, state.layer_states),
        step=jnp.broadcast_to(new_len, state.step.shape))


def prefill(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
            state: ServeState, *, engine=None, attn_chunk: int = 2048
            ) -> Tuple[jax.Array, ServeState]:
    """Sequence prefill that fills the decode caches, returning last-token
    logits. Implemented as a scan of serve_step for state-carrying families
    (correct, if not flash-fast; the prefill_32k dry-run cells lower
    ``forward`` instead, which is the throughput path). This is the
    serving engine's LM prefill: one jitted call replaces the former
    per-token Python loop, and its dispatch plan records one scan-body
    execution — the ledger commits it ``seq_len`` times (DESIGN.md §10.2)."""
    tokens = batch["tokens"]
    s = tokens.shape[1]

    def body(st, t):
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        logits, st = serve_step(params, cfg, tok, st, engine=engine)
        return st, logits

    state, logits = jax.lax.scan(body, state, jnp.arange(s))
    return logits[-1], state
