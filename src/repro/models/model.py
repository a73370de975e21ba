"""Family dispatch: one public API over every assigned architecture.

  init_params(key, cfg, max_positions)        -> param pytree
  forward(params, cfg, batch)                 -> (logits, aux)   train/prefill
  loss_fn(params, cfg, batch)                 -> (loss, metrics)
  init_serve_state(params, cfg, batch, max_len) -> decode state pytree
  serve_step(params, cfg, token, state)       -> (logits, state')  one token

What differs between families — the parameters, the backbone, the decode
state, the request payload and how it prefills — lives in one
``ServingFamily`` per family, chosen from ``cfg.family`` by ``family_of``
(DESIGN.md §11.4). The functions below and ``serve/`` ask that object;
nothing else tells Whisper from the token LMs.

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
import numpy as np

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
    every call, so those leaves — the fields a state type declares in
    ``READ_ONLY`` — come back as ``None``. LM states have no read-only
    leaves and return whole."""
    ls = state.layer_states
    read_only = getattr(ls, "READ_ONLY", ())
    if read_only:
        ls = ls._replace(**dict.fromkeys(read_only))
    return ServeState(layer_states=ls, step=state.step)


def with_step_writes(state: ServeState, written: ServeState) -> ServeState:
    """``state`` with the leaves a decode program wrote taken from
    ``written``; the read-only leaves stay ``state``'s own arrays, by
    reference. ``written`` may also be a full state: only its written
    leaves are read."""
    ls, w = state.layer_states, written.layer_states
    read_only = getattr(ls, "READ_ONLY", ())
    if read_only:
        w = ls._replace(**{f: getattr(w, f) for f in ls._fields
                           if f not in read_only})
    return ServeState(layer_states=w, step=written.step)


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, max_positions: int = 0) -> dict:
    return family_of(cfg).init_params(key, cfg, max_positions)


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
def forward(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            engine=None, attn_chunk: int = 2048
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits, moe_aux_loss)."""
    fam = family_of(cfg)
    h, aux = fam.hidden(params, cfg, batch, engine, attn_chunk)
    return fam.readout(params, cfg, h, engine), aux


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
    fam = family_of(cfg)
    h, aux = fam.hidden(params, cfg, batch, engine, attn_chunk)
    labels = batch["labels"]
    readout = fam.readout

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
    st = family_of(cfg).init_layer_states(params, cfg, batch, max_len,
                                          memory, engine)
    return ServeState(layer_states=st, step=jnp.asarray(prefill_len, jnp.int32))


def serve_step(params: dict, cfg: ModelConfig, token: jax.Array,
               state: ServeState, *, engine=None
               ) -> Tuple[jax.Array, ServeState]:
    """token: (B, 1) i32 -> (logits (B, 1, V), state').

    Trace-pure with an ``engine`` attached (DESIGN.md §10.1): offload
    routing resolves from static shapes at trace time and nothing mutates
    host state, so serve/engine.py jits this step unconditionally
    (regression-tested by tests/test_plan.py)."""
    logits, st = family_of(cfg).decode(params, cfg, token,
                                       state.layer_states, engine)
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
    fam = family_of(cfg)
    fam.require("verify")
    logits, st = fam.verify(params, cfg, tokens, state.layer_states, engine)
    return logits, ServeState(st, state.step + tokens.shape[1])


def set_slot_lengths(state: ServeState, new_len: jax.Array) -> ServeState:
    """Splice per-slot decode positions to ``new_len`` (B,) — the
    speculative rollback (DESIGN.md §17.1): after a verify window
    advanced every length by W, the accepted prefix keeps only
    ``1 + accept_len`` of those tokens, so the counters rewind while the
    over-written KV entries beyond ``new_len`` stay in place (masked by
    the validity test, then overwritten by the next window).

    Structural rule, the inverse discipline of ``slot_layout``: among the
    leaves a decode step writes (``step_writes``), the counters are
    exactly the ``ndim <= 2`` ones — ``step`` (B,) and layer-stacked
    lengths (R, B) — and every data leaf is ``ndim >= 3``, so counters
    broadcast-assign from ``new_len`` and data passes through untouched.
    The read-only leaves pass through too: the paged layout's block and
    cross tables (DESIGN.md §15.2) are ndim-2 *data* leaves, and its
    rejected-suffix *pages* are released host-side by the paged
    scheduler's post-round trim (DESIGN.md §17.4)."""
    new_len = jnp.asarray(new_len, jnp.int32)

    def conv(a):
        if a.ndim == 1:                       # (B,) unstacked counter
            return jnp.broadcast_to(new_len, a.shape)
        if a.ndim == 2:                       # (R, B) layer-stacked counter
            return jnp.broadcast_to(new_len[None, :], a.shape)
        return a

    written = jax.tree_util.tree_map(conv, step_writes(state).layer_states)
    return with_step_writes(state, ServeState(
        layer_states=written,
        step=jnp.broadcast_to(new_len, state.step.shape)))


# ---------------------------------------------------------------------------
# Serving families (DESIGN.md §11.4)
# ---------------------------------------------------------------------------
class ServingFamily:
    """What the model functions above and ``serve/`` need to know about a
    model family: its parameters and backbone, the decode state it carries,
    the request payload and how it prefills, the first decode input, the
    family's part of the plan keys, and what it can serve. The base class
    holds what both families share; ``family_of`` picks the instance."""
    payload_rank = 1         # one request's payload: an (S,) prompt
    payload_dims = "S"
    payload_axis = "seq"     # name of a batch payload's axis 1 (span args)
    serves: frozenset = frozenset()     # "verify" windows, the "paged" pool

    def require(self, what: str) -> None:
        """Raise unless this family serves ``what`` (``"verify"`` windows
        for speculative decoding, the ``"paged"`` pool)."""
        if what not in self.serves:
            raise NotImplementedError(
                f"{what!r} serving is wired for the audio family only (the "
                f"Whisper ladder, DESIGN.md §15, §17); token LMs decode one "
                f"token a step over the contiguous slot pool")

    def request(self, payload: Any, n_frames: Optional[int]) -> np.ndarray:
        """One request's payload as a batch-1 array, fitted to the pool's
        geometry. One request per submit: a stacked batch would splice
        several rows into one slot and corrupt its neighbours' state."""
        arr = np.asarray(payload)
        if arr.ndim == self.payload_rank:
            arr = arr[None]
        if arr.ndim != self.payload_rank + 1 or arr.shape[0] != 1:
            raise ValueError(
                f"submit() takes ONE request — expected shape "
                f"({self.payload_dims},) or batch-1, got {arr.shape}; "
                f"submit rows separately")
        return arr

    def empty_state(self, params: dict, cfg: ModelConfig, batch: int,
                    max_len: int, n_frames: Optional[int]) -> ServeState:
        """A ``batch``-row decode state holding no request — the slot
        pool's initial state, in the standard layout."""
        return init_serve_state(params, cfg, batch, max_len)

    def warm_tuning(self, cfg: ModelConfig, offload, *,
                    quant: Optional[str], **shape) -> int:
        """Pre-tune the GEMM shapes of one inference (DESIGN.md §9.4);
        returns the number of shapes tuned, 0 where the family has no
        enumerated workload."""
        return 0


class _TokenLM(ServingFamily):
    """Every decoder-only family (dense, MoE, SSM, hybrid, VLM): a request
    is a prompt, prefilled by a scan of the one-token step."""

    def init_params(self, key, cfg, max_positions):
        pdtype = {"bfloat16": jnp.bfloat16,
                  "float32": jnp.float32}[cfg.param_dtype]
        ks = jax.random.split(key, 4)
        params = {
            "embed": layers.init_embedding(ks[0], cfg.padded_vocab,
                                           cfg.d_model, pdtype),
            "stack": transformer.init_decoder_stack(ks[1], cfg),
            "final_norm": layers.init_norm(cfg.d_model, cfg.norm, pdtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.init_linear(
                ks[2], cfg.d_model, cfg.padded_vocab, dtype=pdtype)
        if cfg.family == "vlm":
            params["projector"] = layers.init_linear(
                ks[3], cfg.vision_embed_dim, cfg.d_model, bias=True,
                dtype=pdtype)
        return params

    def hidden(self, params, cfg, batch, engine, attn_chunk):
        """The backbone: (hidden states before the readout, MoE aux loss)."""
        x = _embed_inputs(params, cfg, batch, engine)
        positions = jnp.arange(x.shape[1])[None, :]
        return transformer.apply_decoder_stack(params["stack"], cfg, x,
                                               positions=positions,
                                               engine=engine,
                                               attn_chunk=attn_chunk)

    def readout(self, params, cfg, x, engine=None):
        return _readout(params, cfg, x, engine)

    def init_layer_states(self, params, cfg, batch, max_len, memory, engine):
        return transformer.init_decode_state(cfg, batch, max_len, _dtype(cfg))

    def decode(self, params, cfg, token, layer_states, engine):
        x = layers.embed(params["embed"], token).astype(_dtype(cfg))
        x, st = transformer.decode_step_stack(params["stack"], cfg, x,
                                              layer_states, engine=engine)
        return _readout(params, cfg, x, engine), st

    def prefill(self, params, cfg, tokens, max_len, engine):
        """A scan of ``serve_step`` over the prompt that fills the decode
        caches and returns the last logits (correct, if not flash-fast;
        the prefill_32k dry-run cells lower ``forward`` instead, which is
        the throughput path). Its plan records one scan body, so the
        ledger commits it once per prompt token (DESIGN.md §10.2)."""
        state = init_serve_state(params, cfg, tokens.shape[0], max_len)

        def body(st, t):
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, st = serve_step(params, cfg, tok, st, engine=engine)
            return st, logits

        state, logits = jax.lax.scan(body, state, jnp.arange(tokens.shape[1]))
        return logits[-1], state

    def prefill_times(self, payload) -> int:
        return payload.shape[1]

    def first_token(self, out, sot_id, argmax):
        """The greedy pick after the prompt: (B, 1), on the device."""
        return argmax(out[:, -1])[:, None]

    def step_extra(self, n_frames) -> tuple:
        return ()

    def random_batch(self, cfg, rng, batch: int, n_frames: int) -> np.ndarray:
        """``batch`` random 8-token prompts; an LM request has no frames."""
        return rng.integers(0, cfg.vocab_size, (batch, 8)).astype(np.int32)


class _Audio(ServingFamily):
    """Whisper: a request is a mel spectrogram padded to the pool's frame
    capacity, prefilled by the encoder and the per-layer cross-KV
    projection (paper Fig 1); decoding starts from the SOT token."""
    payload_rank = 2
    payload_dims = "F, n_mels"
    payload_axis = "frames"
    serves = frozenset({"verify", "paged"})

    def init_params(self, key, cfg, max_positions):
        return whisper.init_whisper(key, cfg, max_positions)

    def hidden(self, params, cfg, batch, engine, attn_chunk):
        memory = whisper.encode(params, cfg, batch["mel"], engine=engine,
                                attn_chunk=attn_chunk)
        h = whisper.decode_train(params, cfg, batch["tokens"], memory,
                                 engine=engine, attn_chunk=attn_chunk,
                                 return_hidden=True)
        return h, jnp.zeros((), jnp.float32)

    def readout(self, params, cfg, x, engine=None):
        return whisper.readout(params, cfg, x, engine)

    def init_layer_states(self, params, cfg, batch, max_len, memory, engine):
        assert memory is not None, "whisper decode needs encoder memory"
        return whisper.init_whisper_decode_state(params, cfg, memory, max_len,
                                                 engine=engine,
                                                 dtype=_dtype(cfg))

    def decode(self, params, cfg, token, layer_states, engine):
        return whisper.decode_step(params, cfg, token, layer_states,
                                   engine=engine)

    def verify(self, params, cfg, tokens, layer_states, engine):
        return whisper.verify_step(params, cfg, tokens, layer_states,
                                   engine=engine)

    def prefill(self, params, cfg, mel, max_len, engine):
        """The encoder once per utterance batch, then the decode state
        with its cross-K/V projected from the encoder memory."""
        memory = whisper.encode(params, cfg, mel, engine=engine)
        state = init_serve_state(params, cfg, mel.shape[0], max_len,
                                 memory=memory, engine=engine)
        return memory, state

    def prefill_times(self, payload) -> int:
        return 1

    def first_token(self, out, sot_id, argmax):
        """The SOT token seeds every row: (B, 1), on the host."""
        return np.full((out.shape[0], 1), sot_id, np.int32)

    def step_extra(self, n_frames) -> tuple:
        return (n_frames,)

    def request(self, payload, n_frames):
        arr = super().request(payload, n_frames)
        f = arr.shape[1]
        if f > n_frames:
            raise ValueError(f"utterance has {f} frames > pool "
                             f"capacity {n_frames}")
        if f < n_frames:
            arr = np.pad(arr, ((0, 0), (0, n_frames - f), (0, 0)))
        return arr

    def empty_state(self, params, cfg, batch, max_len, n_frames):
        if n_frames is None:
            raise ValueError("audio serving needs n_frames, the pool's fixed "
                             "mel-frame capacity (utterances are padded to "
                             "it)")
        # zeros memory only shapes the cross-KV rows; a splice overwrites
        # them with the request's own. engine=None: building the pool must
        # not touch the offload ledger.
        memory = jnp.zeros((batch, n_frames, cfg.d_model), _dtype(cfg))
        return init_serve_state(params, cfg, batch, max_len, memory=memory)

    def warm_tuning(self, cfg, offload, *, quant, **shape):
        return whisper.warm_tuning(cfg, offload, quant=quant, **shape)

    def random_batch(self, cfg, rng, batch, n_frames):
        """``batch`` random mel spectrograms of ``n_frames`` frames."""
        return rng.standard_normal(
            (batch, n_frames, cfg.n_mels)).astype(np.float32)


TOKEN_LM = _TokenLM()
AUDIO = _Audio()


def family_of(cfg: ModelConfig) -> ServingFamily:
    """The serving family of ``cfg`` — the one place that tells Whisper
    from the token LMs by ``cfg.family``."""
    return AUDIO if cfg.family == "audio" else TOKEN_LM
