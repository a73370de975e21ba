"""Serving engine: batched autoregressive decode with the paper's Q8_0
offload path as a first-class option, plus per-request PDP/EDP accounting.

This is the system the paper builds in whisper.cpp terms: quantized weights
(Q8_0 blocks), the dominant dot-product kernels routed through the offload
dispatcher (core/offload.py — main segment on the accelerator kernel,
residual on the host), everything else on the plain XLA path, and the
energy model (core/energy.py) attributing accelerator-active vs host time
exactly like Eq. 2/3.

Dispatch is trace-pure (DESIGN.md §10): routing resolves at trace time
from static shapes, so prefill and the decode step are wrapped in
``jax.jit`` *unconditionally* — attaching an ``OffloadEngine`` no longer
forces the flagship offloaded configuration onto the slow un-jitted path.
Offload accounting comes from ``DispatchPlan``s recorded per
``(phase, batch, seq, quant)`` key (cached — steady-state requests re-use
them) and committed to the host-side ``OffloadLedger`` multiplied by the
executed step counts.

Request flow (DESIGN.md §11):
  submit(prompt)/submit_audio(mel) -> queued on the continuous-batching
           scheduler (serve/scheduler.py)
  run() -> admits queued requests into freed slots of the fixed-shape
           KV-cache pool *between* jitted decode steps, evicts on
           EOS/max_new, streams tokens as produced, and records wall-time
           and PDP per request.
``generate()``/``transcribe()`` remain the one-shot static-batch path —
prefill the whole batch, decode run-to-completion — used by callers that
already hold a full batch.

Sharded serving (DESIGN.md §13): constructing the engine with a
``mesh`` places the serving weights per ``sharding/rules.py
serve_param_specs`` (TP over "model" where divisible, replicated over
the slot-DP "data" axis), shards the scheduler's slot pool over "data",
appends the mesh signature to every plan key/entry, and reports
per-device FLOP attribution (``energy_report()["dispatch"]["by_device"]``).

Token contract: ``GenerationResult.tokens`` holds exactly the ``steps``
tokens *this request generated*, for both paths — prompt tokens (and the
SOT token) are never included, and rows that hit EOS before the batch
drained are truncated at their first EOS with ``steps`` reported
per-request (not the batch-global step count).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Hashable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import energy
from repro.core.offload import OffloadEngine
from repro.core.plan import DispatchPlan, PlanCache, plan_key, record_plan
from repro.core.qformats import quantize_tree
from repro.models import model as model_lib
from repro.sharding import ctx as shard_ctx
from repro.sharding import rules as shard_rules


@dataclass
class GenerationResult:
    tokens: List[int]       # the ``steps`` generated tokens (no prompt/SOT)
    prefill_s: float
    decode_s: float
    steps: int
    # scheduler-path lifecycle timings (DESIGN.md §16.1): wall time spent
    # queued before admission, and submit -> first streamed token. The
    # one-shot generate()/transcribe() paths have no queue, so both stay
    # at their 0.0 defaults there.
    queue_wait_s: float = 0.0
    ttft_s: float = 0.0

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    def pdp_j(self, power_w: float = energy.TPU_V5E_W) -> float:
        return energy.pdp(self.total_s, power_w)

    def edp_js(self, power_w: float = energy.TPU_V5E_W) -> float:
        return energy.edp(self.total_s, power_w)


def _keep_dense(path, leaf) -> bool:
    """Quantization predicate mirroring whisper.cpp: quantize big GEMM
    weights, keep norms / biases / positional tables / conv / router in
    fp16. Biases are matched by their full leaf name ('b'), NOT a '/b'
    substring (which would swallow everything under '/blocks/')."""
    parts = [str(getattr(k, "key", getattr(k, "name", k))).lower()
             for k in path]
    name = "/".join(parts)
    if parts and parts[-1] in ("b", "bias", "conv_w", "conv_b"):
        return False
    if any(s in name for s in ("norm", "pos", "a_log", "dt_bias", "router")):
        return False
    return True


@dataclass
class ServeEngine:
    cfg: ModelConfig
    params: Any
    max_len: int = 512
    quant: Optional[str] = None          # None -> cfg.quant
    offload: Optional[OffloadEngine] = None
    eos_id: Optional[int] = 0
    # serving mesh (DESIGN.md §13): weights are placed per
    # sharding/rules.serve_param_specs (TP over "model" where divisible,
    # replicated over the slot-DP "data" axis), the scheduler's slot pool
    # shards its slot axis over "data", and every plan key/entry carries
    # the mesh signature. None -> the single-device behavior, unchanged.
    mesh: Optional[Any] = None
    # nullable observability handle (DESIGN.md §16.2): None (the default)
    # keeps every instrumentation site a single ``is not None`` test and
    # allocates no spans; a Telemetry instruments the engine, both
    # schedulers, and the paged pool, binds the offload ledger for
    # span-level FLOP attribution, and becomes the process-global handle
    # the executor's trace-time dispatch counter consults.
    telemetry: Optional[obs.Telemetry] = None
    _serve_params: Any = field(default=None, repr=False)
    _decode_jit: Any = field(default=None, repr=False)
    _step_traces: int = field(default=0, repr=False)
    _verify_traces: int = field(default=0, repr=False)
    _scheduler: Any = field(default=None, repr=False)

    def __post_init__(self):
        q = self.quant if self.quant is not None else self.cfg.quant
        if q == "q8_0":
            self._serve_params = quantize_tree(self.params, _keep_dense)
        else:
            self._serve_params = self.params
        cfg = self.cfg
        # what this engine's family serves and how it prefills
        # (DESIGN.md §11.4)
        self.family = model_lib.family_of(cfg)
        # Pre-tune the canonical single-utterance workload (full 30s
        # window) so the common case never pays a first-invocation sweep
        # (DESIGN.md §9.4); transcribe() re-warms for the actual batch and
        # frame count before its timers start. Warming follows the
        # *resolved* quantization q, which may override cfg.quant.
        self._serve_quant = q
        if (self.offload is not None and self.offload.tuner is not None
                and self.family.warm_tuning(cfg, self.offload, quant=q)):
            self.offload.tuner.save()

        if self.mesh is not None:
            # place serving weights on the mesh (DESIGN.md §13): TP over
            # "model" where dims divide, replicated over the slot-DP
            # "data" axis; Q8_0 qs/scales legs inherit the dense rule
            specs = shard_rules.serve_param_specs(self._serve_params,
                                                  self.mesh)
            self._serve_params = jax.device_put(
                self._serve_params, shard_rules.named(self.mesh, specs))
            if self.offload is not None:
                # stamp the signature into every PlanEntry this engine
                # resolves — sharded plans never equal unsharded ones
                self.offload.mesh_sig = shard_rules.mesh_signature(self.mesh)

        engine = self.offload
        mesh = self.mesh

        def decode_fn(params, token, state):
            # activation_sharding activates at trace time, which is when
            # the executor's ctx.constrain batch anchors bake in
            with shard_ctx.activation_sharding(mesh):
                return model_lib.serve_step(params, cfg, token, state,
                                            engine=engine)

        # dispatch is trace-pure (DESIGN.md §10.1): jit unconditionally,
        # engine attached or not — routing resolves at trace time and all
        # accounting happens via plan commits outside the traced fn
        self._decode_fn = decode_fn
        self._decode_jit = jax.jit(decode_fn)

        eos = -1 if self.eos_id is None else int(self.eos_id)

        def step_fn(params, token, done, state):
            """One greedy decode step with an on-device done-mask: emit
            the argmax token and fold its EOS test into ``done`` without
            leaving the device. Returns only the state it writes
            (``model.step_writes``); callers put it back together with
            ``model.with_step_writes``. Shape-stable across both serving
            modes — the continuous-batching scheduler drives the SAME
            compiled step at its pool width (DESIGN.md §11.2). The trace
            counter increments only when jax re-traces (host code runs at
            trace time), which is how tests and the continuous_batching
            benchmark assert zero retraces after warmup."""
            self._step_traces += 1
            logits, state = decode_fn(params, token, state)
            nxt = self._argmax(logits[:, -1])[:, None]
            done = done | (nxt[:, 0] == eos)
            return nxt, done, model_lib.step_writes(state)

        self._step_jit = jax.jit(step_fn)

        def verify_core(params, tokens, state):
            """The k-position verify step (DESIGN.md §17.1): score a
            (B, W) window in one forward, advancing every cache length
            by W. Lives next to ``_decode_jit`` so the speculative
            engine drives the same compiled-program discipline — one
            trace per (B, W, frames) shape."""
            with shard_ctx.activation_sharding(mesh):
                return model_lib.verify_step(params, cfg, tokens, state,
                                             engine=engine)

        def verify_fn(params, tokens, state):
            # counted exactly like _step_traces (host code runs at trace
            # time); plan recording uses the counter-free _verify_fn so
            # an eval_shape never inflates the zero-retrace gate. Like
            # the step, it returns only the state it writes.
            self._verify_traces += 1
            logits, state = verify_core(params, tokens, state)
            return logits, model_lib.step_writes(state)

        self._verify_fn = verify_core
        self._verify_jit = jax.jit(verify_fn)

        family, max_len = self.family, self.max_len

        def prefill_fn(params, payload):
            """The family's batch prefill (DESIGN.md §11.4): Whisper's
            encoder and cross-KV projection, or an LM's prompt scan."""
            with shard_ctx.activation_sharding(mesh):
                return family.prefill(params, cfg, payload, max_len, engine)

        self._prefill_fn = prefill_fn
        self._prefill_jit = jax.jit(prefill_fn)
        self._plans = PlanCache()

        if self.telemetry is not None:
            # bind AFTER warm_tuning: warmup plan commits predate the
            # consistency window, so span-claimed FLOPs start from zero
            # exactly when the ledger baseline does (DESIGN.md §16.2)
            if self.offload is not None:
                self.telemetry.bind_ledger(self.offload.ledger)
            obs.activate(self.telemetry)

    def _argmax(self, logits: jax.Array) -> jax.Array:
        """Greedy pick over the true vocab (vocab_pad columns excluded)."""
        v = self.cfg.vocab_size
        if logits.shape[-1] > v:
            logits = logits[..., :v]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def _key(self, phase: str, batch: int, *extra: Hashable,
             pages: Optional[Any] = None, role: Optional[str] = None,
             k: Optional[int] = None) -> Hashable:
        """This engine's canonical plan key: ``(phase, quant, batch,
        *extra)`` plus the mesh signature when serving sharded
        (DESIGN.md §13), the page geometry when serving paged
        (DESIGN.md §15.5), and the draft/verify role + window size when
        serving speculatively (DESIGN.md §17.2) — the one-shot paths and
        every scheduler build keys here, so sharded/paged/speculative
        programs at the same shapes land in distinct ``PlanCache``
        entries."""
        return plan_key(phase, self._serve_quant, batch, *extra,
                        mesh=self.mesh, pages=pages, role=role, k=k)

    def _plan(self, key: Hashable, fn, *args) -> Optional[DispatchPlan]:
        """Routing plan for ``fn(*args)``, cached per shape key
        (DESIGN.md §10.3): repeat requests at the same (batch, seq,
        quant) point are dict hits and never re-trace."""
        if self.offload is None:
            return None
        tele = self.telemetry
        if tele is not None and key not in self._plans.plans:
            # trace the one-time plan-build (a real jax trace); cache hits
            # skip the span entirely — they are dict lookups
            with tele.span("plan_build", cat="engine",
                           args={"key": str(key)}):
                return self._plans.get_or_build(
                    key, lambda: record_plan(self.offload, fn, *args,
                                             key=key))
        return self._plans.get_or_build(
            key, lambda: record_plan(self.offload, fn, *args, key=key))

    def _commit(self, plan: Optional[DispatchPlan], times: int,
                role: str) -> None:
        if self.offload is not None:
            self.offload.ledger.commit(plan, times=times, role=role)

    # ------------------------------------------------------------------
    # Admission API (DESIGN.md §11.4): the one place that keys, plans,
    # runs and commits the engine's programs for the schedulers and the
    # speculative engine. None of these waits on the device — each caller
    # keeps its own spans, timers and sync.
    # ------------------------------------------------------------------
    def prefill(self, payload: jax.Array, *, role: str = "main"):
        """Dispatch the batch prefill of ``payload`` — (B, F, n_mels) mels
        or (B, S) prompts — and commit its plan to the ledger under
        ``role`` (``"main"``, or ``"draft"``/``"verify"`` in a speculative
        engine). Returns the program's ``(out, state)``: the encoder
        memory or the last logits, and the standard-layout decode state."""
        key = self._key("prefill", payload.shape[0], payload.shape[1])
        fresh = key not in self._plans.plans
        plan = self._plan(key, self._prefill_fn, self._serve_params, payload)
        if fresh and plan is not None and self.telemetry is not None:
            # gauged once per prefill plan, from its shapes: the most
            # times any prefill kernel dequantizes its weights
            g = self.telemetry.metrics.gauge("repro_q8_weight_passes")
            g.set(max(g.value(), plan.q8_weight_passes()))
        out, state = self._prefill_jit(self._serve_params, payload)
        self._commit(plan, self.family.prefill_times(payload), role)
        return out, state

    def first_token(self, out: jax.Array, sot_id: int):
        """The first decode input after a prefill whose output is ``out``:
        (B, 1) int32 — the SOT token (on the host) for Whisper, the greedy
        pick after the prompt (on the device) for an LM."""
        return self.family.first_token(out, sot_id, self._argmax)

    def replay(self, state, tokens: List[int],
               n_frames: Optional[int] = None, *, role: str = "main"):
        """Feed ``tokens`` one at a time through the batch-1 decode from
        ``state`` — the preempt-and-recompute replay (DESIGN.md §15.5,
        §17.4) — and commit that many executions of the step's plan under
        ``role``. Returns the advanced state."""
        plan = self.program_plan(1, state, n_frames, role=role)
        for t in tokens:
            _, state = self._decode_jit(self._serve_params,
                                        jnp.full((1, 1), t, jnp.int32), state)
        self._commit(plan, len(tokens), role)
        return state

    def program_plan(self, batch: int, state, n_frames: Optional[int] = None,
                     *, k: Optional[int] = None, pages: Optional[Any] = None,
                     role: str = "main") -> Optional[DispatchPlan]:
        """The plan of the decode step over ``state`` at ``batch`` rows or,
        given ``k``, of the verify program over a (batch, k+1) window
        (DESIGN.md §17.1), keyed with the pool's page geometry ``pages``
        (DESIGN.md §15.5) and a speculative ``role`` (DESIGN.md §17.2)."""
        extra = self.family.step_extra(n_frames)
        key_role = None if role == "main" else role
        if k is None:
            key = self._key("step", batch, *extra, pages=pages, role=key_role)
            fn, width = self._decode_fn, 1
        else:
            key = self._key("verify", batch, *extra, pages=pages,
                            role=key_role, k=k)
            fn, width = self._verify_fn, k + 1
        return self._plan(key, fn, self._serve_params,
                          jax.ShapeDtypeStruct((batch, width), jnp.int32),
                          state)

    def _greedy_loop(self, state, first_token: jax.Array,
                     max_new: int) -> Dict[str, Any]:
        b = first_token.shape[0]
        token = first_token
        done = jnp.zeros((b,), bool)
        toks = []
        t0 = time.perf_counter()
        steps = 0
        for _ in range(max_new):
            token, done, written = self._step_jit(self._serve_params,
                                                  token, done, state)
            state = model_lib.with_step_writes(state, written)
            toks.append(token)
            steps += 1
            if bool(done.all()):
                break
        jax.block_until_ready(token)
        out = (np.concatenate([np.asarray(t) for t in toks], axis=1)
               if toks else np.zeros((b, 0), np.int32))
        return {"tokens": out, "decode_s": time.perf_counter() - t0,
                "steps": steps, "state": state}

    def _finalize(self, r: Dict[str, Any], prefill_s: float
                  ) -> List[GenerationResult]:
        """Per-request results from a batch greedy loop: each row is
        truncated at its first EOS (inclusive — matching what a batch-1
        run of the same request returns) and ``steps`` is that row's own
        generated count, NOT the batch-global step count. Rows that never
        hit EOS keep all ``r['steps']`` tokens."""
        out = r["tokens"]
        b = out.shape[0]
        eos = self.eos_id
        results = []
        for i in range(b):
            row = out[i].tolist()
            if eos is not None and eos in row:
                row = row[:row.index(eos) + 1]
            results.append(GenerationResult(
                tokens=row, prefill_s=prefill_s / b,
                decode_s=r["decode_s"] / b, steps=len(row)))
        return results

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new: int = 32
                 ) -> List[GenerationResult]:
        """One static batch, prefilled together and decoded to completion:
        (B, S_prompt) int32 prompts (already padded) for LM families, or
        (B, F, n_mels) mels seeded with SOT 1 for Whisper. Returns one
        result per request; ``tokens`` are the generated tokens only (see
        the module-level token contract)."""
        return self._one_shot(prompts, max_new, sot_id=1)

    def transcribe(self, mel: np.ndarray, sot_id: int = 1,
                   max_new: int = 32) -> List[GenerationResult]:
        """Whisper path: encoder once per utterance batch, cross-KV cached,
        autoregressive decode (paper Fig 1). ``tokens`` are the generated
        tokens only (the SOT seed token is not echoed back) — identical
        contract to ``generate()``."""
        return self._one_shot(mel, max_new, sot_id)

    def _one_shot(self, payload: np.ndarray, max_new: int, sot_id: int
                  ) -> List[GenerationResult]:
        """Prefill one static batch and decode it to completion."""
        fam = self.family
        if np.ndim(payload) != fam.payload_rank + 1:
            raise ValueError(f"expected a batch of ({fam.payload_dims}) "
                             f"payloads, got shape {np.shape(payload)}")
        b, n = payload.shape[0], payload.shape[1]
        if self.offload is not None and self.offload.tuner is not None:
            # warm the *actual* batch/frame-count keys (the construction-
            # time warm covers only the canonical 1x1500 shapes) so tuning
            # searches never land inside the timed request; repeat calls
            # are pure cache hits. Persist only when new winners appeared.
            tuner = self.offload.tuner
            n0 = tuner.searches
            fam.warm_tuning(self.cfg, self.offload, n_frames=n, batch=b,
                            n_tokens=max_new, quant=self._serve_quant)
            if tuner.searches > n0:
                tuner.save()
        payload = jnp.asarray(payload)
        t0 = time.perf_counter()
        with obs.maybe_span(self.telemetry, "prefill", cat="engine",
                            ledger=True,
                            args={"batch": b, fam.payload_axis: n}):
            # committing inside the ledger span attributes these FLOPs to
            # the prefill
            out, state = self.prefill(payload)
            jax.block_until_ready(out)
            first = self.first_token(out, sot_id)
            prefill_s = time.perf_counter() - t0
        first = jnp.asarray(first)
        step_plan = self.program_plan(b, state, n)
        with obs.maybe_span(self.telemetry, "decode", cat="engine",
                            ledger=True, args={"batch": b}):
            r = self._greedy_loop(state, first, max_new)
            self._commit(step_plan, r["steps"], "main")
        return self._finalize(r, prefill_s)

    # ------------------------------------------------------------------
    # Continuous batching (DESIGN.md §11) — thin wrappers over the slot
    # scheduler; generate()/transcribe() above stay the one-shot path.
    # ------------------------------------------------------------------
    def scheduler(self, n_slots: Optional[int] = None,
                  n_frames: Optional[int] = None):
        """The engine's continuous-batching scheduler. With no arguments
        (or matching geometry) the existing scheduler is returned; an
        explicit geometry CHANGE rebuilds the pool, refusing while the old
        scheduler still holds queued/active requests or unclaimed results.
        Audio engines need ``n_frames`` — the slot pool's fixed mel
        capacity — on first creation (the submit_audio wrapper infers it
        from the first utterance)."""
        from repro.serve.scheduler import ContinuousBatchingScheduler
        s = self._scheduler
        # dimensions left as None inherit from the live scheduler — an
        # n_frames-only change keeps the slot width and vice versa
        want_slots = n_slots if n_slots is not None else \
            (s.n_slots if s is not None else 4)
        want_frames = n_frames if n_frames is not None else \
            (s.n_frames if s is not None else None)
        if (s is None or s.n_slots != want_slots
                or s.n_frames != want_frames):
            if s is not None and (s.n_queued or s.n_active or s.finished):
                raise RuntimeError(
                    "scheduler geometry change with requests in flight or "
                    "unclaimed results — drain with run() first")
            self._scheduler = ContinuousBatchingScheduler(
                self, n_slots=want_slots, n_frames=want_frames)
        return self._scheduler

    def speculative(self, draft_cfg: ModelConfig, draft_params: Any, *,
                    k: int = 4, draft_quant: str = "none"):
        """A speculative-decoding engine over this verifier
        (serve/speculative.py, DESIGN.md §17): ``draft_cfg``/``draft_params``
        is the cheap ladder model (whisper-tiny against a base/small
        verifier) that proposes ``k`` tokens per round; this engine's
        jitted verify step scores the k+1 window and greedy acceptance
        keeps output token-exact with ``transcribe()`` alone.

        The draft model runs dense on the cheapest backend by default: its
        dispatcher pins ``xla_ref`` (prefer_pallas=False translated by the
        registry, DESIGN.md §12.3) while the verifier keeps its own
        pallas/offload routing — and both share ONE ``OffloadLedger`` so
        the by_role split and the §16.2 span exactness cover the whole
        two-model engine.

        The returned engine serves three ways: ``transcribe()`` for a
        one-shot batch, ``.continuous(n_slots, n_frames)`` for
        round-boundary admission over the §11 slot pool, and
        ``.paged(n_slots, n_frames, **geom)`` for speculative rounds over
        the §15 paged arenas with preempt-and-recompute (DESIGN.md
        §17.4)."""
        from repro.serve.speculative import SpeculativeEngine
        draft_offload = None
        if self.offload is not None:
            draft_offload = OffloadEngine(
                vmem_budget_kb=self.offload.vmem_budget_kb,
                burst=self.offload.burst,
                prefer_pallas=False,            # cheapest backend pin
                interpret=self.offload.interpret,
                ledger=self.offload.ledger)     # ONE ledger, two models
        draft = ServeEngine(draft_cfg, draft_params, max_len=self.max_len,
                            quant=draft_quant, offload=draft_offload,
                            eos_id=self.eos_id, mesh=self.mesh)
        return SpeculativeEngine(verifier=self, draft=draft, k=k)

    def paged_scheduler(self, n_slots: int = 4,
                        n_frames: Optional[int] = None, **page_cfg):
        """A paged-pool continuous-batching scheduler over this engine
        (serve/paging.py, DESIGN.md §15): fixed page arenas instead of
        per-slot preallocation, whole-utterance prefix sharing, and
        admission control that oversubscribes logical slots against
        physical pages with preempt-and-recompute. Built fresh per call —
        page geometry (``page_size``, ``n_pages``, ``cross_page_size``,
        ``n_cross_pages``) is workload-tuned and the caller owns the
        instance; the cached ``scheduler()`` stays the contiguous path."""
        from repro.serve.paging import PagedScheduler
        return PagedScheduler(self, n_slots=n_slots, n_frames=n_frames,
                              **page_cfg)

    def submit(self, prompt: np.ndarray, max_new: int = 32, *,
               n_slots: Optional[int] = None) -> int:
        """Queue one LM prompt (S,) / (1, S) on the scheduler."""
        return self.scheduler(n_slots).submit(prompt, max_new=max_new)

    def submit_audio(self, mel: np.ndarray, max_new: int = 32, *,
                     n_slots: Optional[int] = None,
                     n_frames: Optional[int] = None, sot_id: int = 1) -> int:
        """Queue one utterance (F, n_mels) / (1, F, n_mels); padded to the
        pool's frame capacity. ``n_frames`` fixes that capacity on first
        call — omitted, it is inferred from this utterance's frame count
        (later, longer utterances then need a fresh scheduler)."""
        if self._scheduler is None and n_frames is None:
            arr = np.asarray(mel)
            n_frames = int(arr.shape[0] if arr.ndim == 2 else arr.shape[1])
        return self.scheduler(n_slots, n_frames).submit(
            mel, max_new=max_new, sot_id=sot_id)

    def run(self, on_token=None) -> Dict[int, GenerationResult]:
        """Drain the scheduler: admit/decode/evict until queue and slots
        are empty, streaming tokens through ``on_token``. Returns
        {request id: GenerationResult}."""
        if self._scheduler is None:
            return {}
        return self._scheduler.run(on_token=on_token)

    # ------------------------------------------------------------------
    def energy_report(self, results: List[GenerationResult],
                      platform_w: float = energy.TPU_V5E_W) -> Dict[str, float]:
        total_s = sum(r.total_s for r in results)
        rep = {
            "requests": len(results),
            "total_s": total_s,
            "mean_s": total_s / max(len(results), 1),
            "pdp_j": energy.pdp(total_s, platform_w),
            "edp_js": energy.edp(total_s, platform_w),
            "offload_rate": (self.offload.stats.offload_rate()
                             if self.offload else 0.0),
        }
        if self.offload is not None:
            rep["dispatch"] = {"plans": len(self._plans),
                               "plan_hits": self._plans.hits,
                               "plan_misses": self._plans.misses,
                               "ledger_commits": self.offload.ledger.commits,
                               # per-backend call attribution from the
                               # plan-pinned backends (DESIGN.md §12.3)
                               "by_backend": dict(
                                   self.offload.stats.by_backend),
                               # per-device FLOP attribution under sharded
                               # serving (DESIGN.md §13); sums to the
                               # offloaded+fallback+residual flop total
                               "by_device": dict(
                                   self.offload.stats.by_device),
                               # per-role FLOP attribution for multi-model
                               # (speculative) engines (DESIGN.md §17.2);
                               # sums to the same flop total
                               "by_role": dict(
                                   self.offload.stats.by_role)}
        if self.offload is not None and self.offload.tuner is not None:
            t = self.offload.tuner
            rep["tuning"] = {"cache_hits": t.cache.hits,
                             "cache_misses": t.cache.misses,
                             "searches": t.searches,
                             "tuned_calls": self.offload.stats.tuned_calls}
        return rep
