"""Continuous-batching serve scheduler (DESIGN.md §11).

``ServeEngine.generate``/``transcribe`` decode static run-to-completion
batches: finished utterances keep burning jitted steps and new arrivals
head-of-line block until the whole batch drains — exactly the utilization
loss the paper's sustained multi-utterance evaluation (and the ROADMAP's
heavy-traffic north star) forbids. This scheduler decodes a fixed-width
slot batch instead (width ``n_slots`` static, so the engine's jitted
``step_fn`` and its ``PlanCache``/ledger machinery keep working with zero
retraces), admits queued requests into freed slots *between* steps, evicts
on EOS/max_new, and streams per-request tokens as they are produced.

Mechanics per step (DESIGN.md §11.2):
  admit   — one jitted batch-1 prefill per queued request (whisper
            encoder + cross-KV, or LM prompt scan), spliced into a free
            slot by ``kvcache.slot_insert``; prefill wall-time and its
            dispatch-plan ledger commit are attributed to that request
            exactly.
  decode  — ONE execution of the engine's fixed-shape ``step_fn`` over
            all ``n_slots`` rows (free slots compute garbage — the
            fixed-shape contract); its plan commits once per executed
            step, and its wall-time is split over the slots active that
            step, so per-request PDP attribution is exact-by-steps-lived
            rather than batch-averaged, and per-request totals sum to the
            batch total (DESIGN.md §11.3).
  evict   — EOS or ``max_new`` reached: the request's ``GenerationResult``
            is finalized from its per-slot step counter and the slot is
            returned to the free list (its row is overwritten whole by
            the next admission; ``kvcache.slot_reset`` exists for callers
            that want freed rows zeroed eagerly).

Plans come from the engine's admission API (DESIGN.md §11.4) and are
shared with the one-shot paths (DESIGN.md §11.3): the slot-batched step at
``(n_slots, n_frames)`` IS the static decode step at that shape, so no
plan is ever re-recorded. What differs between Whisper and the token LMs
— the payload, its padding, the first token — the engine's serving
family answers.
With a serving mesh attached (DESIGN.md §13) the pool's slot axis shards
over the mesh's "data" axis, admission targets device-local slot ranges
(``SlotKVPool.acquire`` balances across shards), and every plan key
carries the mesh signature so sharded steps never reuse unsharded plans.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as model_lib
from repro.serve.engine import GenerationResult, ServeEngine
from repro.serve.kvcache import SlotKVPool


@dataclass
class TokenEvent:
    """One streamed token: produced by request ``rid`` at its (1-based)
    per-request step ``step``; ``done`` marks the request's last token."""
    rid: int
    token: int
    step: int
    done: bool


@dataclass
class _QueuedRequest:
    rid: int
    payload: np.ndarray          # (1, F, n_mels) mel | (1, S) i32 prompt
    max_new: int
    sot_id: int = 1
    submit_t: float = 0.0        # perf_counter at submit: queue-wait base


@dataclass
class _ActiveSlot:
    rid: int
    max_new: int
    tokens: List[int] = field(default_factory=list)
    steps: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # lifecycle timings (DESIGN.md §16.1), carried into GenerationResult
    submit_t: float = 0.0
    queue_wait_s: float = 0.0
    ttft_s: float = 0.0


class ContinuousBatchingScheduler:
    """Slot-batched continuous decode over a ``ServeEngine``.

    The engine supplies the jitted prefill/step functions, the serving
    params, the plan cache, and the offload ledger; the scheduler owns the
    ``SlotKVPool``, the admission queue, and per-request attribution.
    ``n_frames`` (audio only) fixes the pool's mel-frame capacity —
    admitted utterances are zero-padded to it so prefill and the slot
    splice see one static shape (real Whisper pads every utterance to the
    30 s window the same way).
    """

    def __init__(self, engine: ServeEngine, n_slots: int = 4,
                 n_frames: Optional[int] = None):
        self.engine = engine
        # the engine's nullable telemetry handle (DESIGN.md §16.2) — every
        # instrumentation site below is one ``is not None`` test when off
        self.telemetry = engine.telemetry
        if self.telemetry is not None:
            # pre-resolved per-step instruments + a change-gated gauge
            # cache: decode_step is the hot loop the ≤3% overhead budget
            # (benchmarks/telemetry_overhead.py) prices, so it must not
            # pay a registry lookup per metric per step
            m = self.telemetry.metrics
            self._step_instruments = (m.counter("repro_tokens_total"),
                                      m.histogram("repro_step_seconds"))
            self._step_gauges = (m.gauge("repro_queue_depth"),
                                 m.gauge("repro_slots_active"),
                                 m.gauge("repro_step_traces"),
                                 m.gauge("repro_kv_utilization"))
            self._gauge_state = None
            # per-step metric observations buffer in plain lists/ints on
            # the hot path and drain into the registry off it (run()/
            # attribution()/flush_telemetry) — registry calls are ~1-2 µs
            # each cold, and a decode step makes several (DESIGN.md §16.4)
            self._buf_steps: List[float] = []
            self._buf_ttft: List[float] = []
            self._buf_tokens = 0
            self._buf_finished = 0
        self.n_slots = n_slots
        self.n_frames = n_frames
        self.pool = self._make_pool()
        self.queue: Deque[_QueuedRequest] = deque()
        self.finished: Dict[int, GenerationResult] = {}
        self._active: Dict[int, _ActiveSlot] = {}      # slot -> request
        # device-resident next-token buffer: decode feeds the previous
        # step's output back without a host->device upload per step
        self._tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self._done0 = jnp.zeros((n_slots,), bool)      # step_fn done input
        if engine.mesh is not None and self.pool.n_shards > 1:
            # pin the per-slot buffers to the pool's slot sharding so the
            # sharded decode step reads device-local tokens (DESIGN.md §13)
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = engine.mesh
            self._tokens = jax.device_put(
                self._tokens, NamedSharding(mesh, P("data", None)))
            self._done0 = jax.device_put(
                self._done0, NamedSharding(mesh, P("data")))
        if self.telemetry is not None:
            # each admission's splice writes one request's row into the
            # pool in place (DESIGN.md §11.1); gauged once, from shapes
            self.telemetry.gauge("repro_splice_written_bytes",
                                 self._splice_written_bytes())
        self._next_rid = 0
        self._step_plan_ready = False
        self._step_plan = None
        # independently accumulated busy wall-time (every prefill + every
        # batch step, measured whole): the other side of the §11.3
        # attribution invariant, NOT derived from per-request shares.
        # _claimed_s is the busy time of results already handed out by
        # run(), so attribution stays exact across claim cycles.
        self._busy_s = 0.0
        self._claimed_s = 0.0
        # KV memory accounting (DESIGN.md §15.4): peak bytes of committed
        # state holding live request data, and peak concurrent admissions —
        # the serving benchmarks report kv_utilization = used_peak/committed
        self.kv_used_peak = 0
        self.active_peak = 0
        self._kv_committed: Optional[int] = None

    def _make_pool(self):
        """Pool factory — the paged scheduler (serve/paging.py,
        DESIGN.md §15) overrides this to swap in its ``PagedKVPool`` while
        inheriting the whole admit/decode/evict loop."""
        eng = self.engine
        return SlotKVPool(eng.cfg, eng._serve_params, self.n_slots,
                          eng.max_len, n_frames=self.n_frames,
                          mesh=eng.mesh)

    def _splice_written_bytes(self) -> int:
        """Bytes one admission's splice writes into the pool, from the
        shapes of a batch-1 decode state (the family's empty state at
        batch 1, shaped as the prefill builds it) spliced into this
        pool."""
        eng = self.engine
        req = jax.eval_shape(
            lambda p: eng.family.empty_state(p, eng.cfg, 1, eng.max_len,
                                             self.n_frames),
            eng._serve_params)
        return model_lib.state_kv_bytes(self.pool.splice_writes(req))

    # -- KV accounting (DESIGN.md §15.4) --------------------------------
    @property
    def kv_committed_bytes(self) -> int:
        # cached: the pool's committed state is fixed-shape buffers
        # allocated at construction, but measuring it walks the whole
        # state pytree — far too slow for the per-step gauge update
        if self._kv_committed is None:
            self._kv_committed = self.pool.committed_kv_bytes()
        return self._kv_committed

    @property
    def kv_utilization_peak(self) -> float:
        c = self.kv_committed_bytes
        return self.kv_used_peak / c if c else 0.0

    def _note_kv_usage(self) -> None:
        """Sample KV usage at this step's height: every active slot is
        about to write (or just wrote) position ``steps``, so it holds
        ``steps + 1`` live entries."""
        lengths = {s: a.steps + 1 for s, a in self._active.items()}
        used = self.pool.used_kv_bytes(lengths)
        if used > self.kv_used_peak:
            self.kv_used_peak = used
        if len(self._active) > self.active_peak:
            self.active_peak = len(self._active)

    # -- queue ----------------------------------------------------------
    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_queued(self) -> int:
        return len(self.queue)

    @property
    def step_traces(self) -> int:
        """How often the engine's decode step_fn was traced — stays at 1
        after warmup for any admission schedule (tests/test_scheduler.py)."""
        return self.engine._step_traces

    def submit(self, payload: np.ndarray, max_new: int = 32,
               sot_id: int = 1) -> int:
        """Queue one request; returns its request id. ``payload`` is a
        mel (F, n_mels) / (1, F, n_mels) for audio engines (padded to the
        pool's ``n_frames``) or an int prompt (S,) / (1, S) for LMs."""
        arr = self.engine.family.request(payload, self.n_frames)
        rid = self._next_rid
        self._next_rid += 1
        if max_new <= 0:
            # zero-budget requests finish immediately with the empty
            # result the one-shot path returns for max_new=0 — they never
            # occupy a slot (and skip the pointless prefill)
            self.finished[rid] = GenerationResult(tokens=[], prefill_s=0.0,
                                                  decode_s=0.0, steps=0)
            return rid
        self.queue.append(_QueuedRequest(rid, arr, max_new, sot_id,
                                         submit_t=time.perf_counter()))
        tele = self.telemetry
        if tele is not None:
            tele.instant("submit", rid=rid)
            tele.begin(rid, "queued")
            tele.inc("repro_requests_submitted_total")
            tele.gauge("repro_queue_depth", len(self.queue))
        return rid

    # -- admission ------------------------------------------------------
    def admit(self) -> List[int]:
        """Admit queued requests into free slots (one jitted batch-1
        prefill each, spliced in-place between decode steps). Returns the
        admitted request ids. Spans (DESIGN.md §16.1): ``admit`` around
        the pass; per request ``upload`` (the padded payload to the
        device), ``prefill`` (plan lookup, the program, its sync and the
        ledger commit) and ``splice`` (slot, pool insert, token table)."""
        admitted = []
        with obs.maybe_span(self.telemetry, "admit", cat="sched"):
            while self.queue and self.pool.n_free:
                admitted.append(self._admit_one(self.queue.popleft()))
        return admitted

    def _admit_one(self, req: _QueuedRequest) -> int:
        tele = self.telemetry
        rid = req.rid
        queue_wait = (time.perf_counter() - req.submit_t
                      if req.submit_t else 0.0)
        if tele is not None:
            tele.end(rid, "queued", wait_s=queue_wait)
            tele.observe("repro_queue_wait_seconds", queue_wait)
        state, first, prefill_s = self._prefill(req)
        with obs.maybe_span(tele, "splice", cat="lifecycle",
                            track=obs.request_track(rid), rid=rid):
            slot = self.pool.acquire()
            self.pool.insert(slot, state)
            self._tokens = self._tokens.at[slot, 0].set(first)
        self._active[slot] = _ActiveSlot(rid=rid, max_new=req.max_new,
                                         prefill_s=prefill_s,
                                         submit_t=req.submit_t,
                                         queue_wait_s=queue_wait)
        if tele is not None:
            tele.begin(rid, "decode")
        return rid

    def _prefill(self, req: _QueuedRequest):
        """One request's ``upload`` and ``prefill`` spans: its batch-1
        prefill state, its first token and the prefill's wall time (the
        program, its sync and the first token), which counts as busy."""
        tele = self.telemetry
        track = obs.request_track(req.rid)
        with obs.maybe_span(tele, "upload", cat="lifecycle", track=track,
                            rid=req.rid):
            payload = jnp.asarray(req.payload)
        # the ledger span tightly scopes this request's prefill exec +
        # commit, so its FLOP delta IS the prefill's attribution
        with obs.maybe_span(tele, "prefill", cat="lifecycle", track=track,
                            rid=req.rid, ledger=True):
            t0 = time.perf_counter()
            out, state = self.engine.prefill(payload)
            jax.block_until_ready(out)
            first = np.asarray(self.engine.first_token(out, req.sot_id))
            prefill_s = time.perf_counter() - t0
            self._busy_s += prefill_s
        if tele is not None:
            tele.observe("repro_prefill_seconds", prefill_s)
        return state, int(first[0, 0]), prefill_s

    # -- decode ---------------------------------------------------------
    def _ensure_step_plan(self) -> None:
        """Build the step's plan once per pool (keyed with the page
        geometry for a paged pool, DESIGN.md §15.5) and gauge the bytes
        the step returns against those it leaves with the pool."""
        if self._step_plan_ready:
            return
        eng = self.engine
        self._step_plan = eng.program_plan(self.n_slots, self.pool.state,
                                           self.n_frames,
                                           pages=self.pool.plan_geometry)
        self._step_plan_ready = True
        tele = self.telemetry
        if tele is not None:
            # the step returns what it writes (DESIGN.md §11.2); the rest
            # of the pool state stays with the pool, by reference
            state = self.pool.state
            out = jax.eval_shape(eng._step_jit, eng._serve_params,
                                 self._tokens, self._done0, state)
            kept = (model_lib.state_kv_bytes(state)
                    - model_lib.state_kv_bytes(model_lib.step_writes(state)))
            tele.gauge("repro_step_written_bytes",
                       model_lib.state_kv_bytes(out))
            tele.gauge("repro_step_kept_bytes", kept)

    def decode_step(self) -> List[TokenEvent]:
        """One fixed-shape batch decode step: every slot advances (free
        slots compute garbage that is never read), active slots emit their
        next token, finished requests are evicted. Returns the step's
        ``TokenEvent`` stream in slot order. Spans (DESIGN.md §16.1):
        ``decode_step``, the step's ledger span, around ``step.kv_usage``,
        ``step.dispatch`` (the program call returning), ``step.sync`` (the
        host copy of the tokens), ``step.ledger`` and ``step.emit`` (the
        per-slot tokens, results and releases)."""
        if not self._active:
            return []
        tele = self.telemetry
        eng = self.engine
        # the batch step's ledger span scopes exec + host sync + the one
        # plan commit — its FLOP delta is the step's exact attribution
        with obs.maybe_span(tele, "decode_step", cat="step", ledger=True,
                            args={"active": len(self._active)}), \
                obs.phases(tele, cat="step") as ph:
            ph("step.kv_usage")
            self._note_kv_usage()
            ph("step.dispatch")
            self._ensure_step_plan()
            t0 = time.perf_counter()
            nxt, _, written = eng._step_jit(eng._serve_params, self._tokens,
                                            self._done0, self.pool.state)
            self.pool.state = model_lib.with_step_writes(self.pool.state,
                                                         written)
            self._tokens = nxt
            ph("step.sync")
            nxt_np = np.asarray(nxt)                   # host sync: streaming
            dt = time.perf_counter() - t0
            self._busy_s += dt
            ph("step.ledger")
            if eng.offload is not None:
                eng.offload.ledger.commit(self._step_plan, times=1)
            ph("step.emit")
            events = self._emit(tele, nxt_np, dt)
        if tele is not None:
            self._buf_tokens += len(events)
            self._buf_steps.append(dt)
            # change-gate on the plain-int peak, not the utilization
            # property — the ratio's denominator walks the state pytree
            g = (len(self.queue), len(self._active), eng._step_traces,
                 self.kv_used_peak)
            if g != self._gauge_state:      # gauges move rarely mid-drain
                self._gauge_state = g
                gq, gs, gt, gu = self._step_gauges
                gq.set(g[0])
                gs.set(g[1])
                gt.set(g[2])
                gu.set(self.kv_utilization_peak)
        return events

    def _emit(self, tele, nxt_np: np.ndarray, dt: float) -> List[TokenEvent]:
        share = dt / len(self._active)
        now = time.perf_counter()
        eos = self.engine.eos_id
        events = []
        for slot in sorted(self._active):
            a = self._active[slot]
            tok = int(nxt_np[slot, 0])
            a.tokens.append(tok)
            a.steps += 1
            a.decode_s += share
            if a.steps == 1 and a.ttft_s == 0.0 and a.submit_t > 0.0:
                # first generated token of this request: TTFT is wall time
                # from submit, inclusive of queue wait and prefill
                a.ttft_s = now - a.submit_t
                if tele is not None:
                    self._buf_ttft.append(a.ttft_s)
            done = a.steps >= a.max_new or (eos is not None and tok == eos)
            events.append(TokenEvent(a.rid, tok, a.steps, done))
            if done:
                self.finished[a.rid] = GenerationResult(
                    tokens=a.tokens, prefill_s=a.prefill_s,
                    decode_s=a.decode_s, steps=a.steps,
                    queue_wait_s=a.queue_wait_s, ttft_s=a.ttft_s)
                if tele is not None:
                    tele.instant("evict", rid=a.rid)
                    tele.end(a.rid, "decode", steps=a.steps)
                    self._buf_finished += 1
                del self._active[slot]
                # reset=False: insert() fully overwrites the slot on the
                # next admission and freed rows' garbage is never read —
                # skipping the reset saves a dispatch per eviction
                self.pool.release(slot, reset=False)
        return events

    # -- telemetry flush -------------------------------------------------
    def flush_telemetry(self) -> None:
        """Drain the buffered per-step metric observations into the
        registry (DESIGN.md §16.4). The hot path appends to plain lists
        and bumps plain ints; the registry work (label resolution, bucket
        search) happens here, off the per-token latency path. Called by
        ``run()`` and ``attribution()``; drive it yourself after a manual
        ``admit()``/``decode_step()`` loop before reading metrics."""
        tele = self.telemetry
        if tele is None:
            return
        ctok, hstep = self._step_instruments
        if self._buf_tokens:
            ctok.inc(self._buf_tokens)
            self._buf_tokens = 0
        for v in self._buf_steps:
            hstep.observe(v)
        self._buf_steps.clear()
        for v in self._buf_ttft:
            tele.observe("repro_ttft_seconds", v)
        self._buf_ttft.clear()
        if self._buf_finished:
            tele.inc("repro_requests_finished_total", self._buf_finished)
            tele.inc("repro_evictions_total", self._buf_finished)
            self._buf_finished = 0

    # -- drain ----------------------------------------------------------
    def run(self, on_token: Optional[Callable[[TokenEvent], Any]] = None
            ) -> Dict[int, GenerationResult]:
        """Drain queue + slots to completion, streaming each token through
        ``on_token`` as it is produced. Returns {rid: GenerationResult}
        and CLAIMS those results — each result is handed out exactly once,
        so a long-running submit()/run() loop holds no unbounded history
        (results produced via manual admit()/decode_step() driving stay in
        ``finished`` until a run() claims them)."""
        while self.queue or self._active:
            self.admit()
            for ev in self.decode_step():
                if on_token is not None:
                    on_token(ev)
        out = dict(self.finished)
        self.finished.clear()
        self._claimed_s += sum(r.total_s for r in out.values())
        self.flush_telemetry()
        return out

    # -- attribution (DESIGN.md §11.3) ----------------------------------
    def attribution(self, power_w: Optional[float] = None) -> Dict[str, Any]:
        """Per-request PDP attribution: each finished request's PDP from
        its exact prefill time + its share of every step it was live for.
        The contract: per-request PDP sums to the batch total, where the
        batch total comes from the INDEPENDENTLY accumulated busy
        wall-time (whole prefills + whole batch steps, never per-request
        shares) — a mis-split in the share bookkeeping breaks the
        equality rather than cancelling out. Exact once all requests have
        drained (live slots still hold unfinalized shares); asserted by
        benchmarks/continuous_batching.py and tests/test_scheduler.py.
        Covers the UNCLAIMED results: busy time of results already handed
        out by run() is subtracted, so the invariant holds per claim
        window in a long-running serve loop."""
        from repro.core import energy
        self.flush_telemetry()
        w = energy.TPU_V5E_W if power_w is None else power_w
        per_req = {rid: r.pdp_j(w) for rid, r in self.finished.items()}
        window_s = self._busy_s - self._claimed_s
        return {"per_request_pdp_j": per_req,
                # lifecycle timings (DESIGN.md §16.1): wall queue wait and
                # submit->first-token per unclaimed finished request, so
                # launch/serve.py prints ONE consolidated report
                "per_request_queue_wait_s": {
                    rid: r.queue_wait_s for rid, r in self.finished.items()},
                "per_request_ttft_s": {
                    rid: r.ttft_s for rid, r in self.finished.items()},
                "batch_pdp_j": energy.pdp(window_s, w),
                "busy_s": window_s,
                "drained": not (self._active or self.queue)}
