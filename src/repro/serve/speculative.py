"""Speculative decoding across the Whisper ladder (DESIGN.md §17).

The paper's scaling study runs tiny -> base -> small, and its PDP
advantage narrows exactly where steps get expensive (32KB local-memory
coverage drops from ~94% on tiny to ~66% on base/small). This module
spends cheap tiny-model FLOPs to amortize those expensive steps: a
``SpeculativeEngine`` drafts ``k`` tokens per request with the ladder's
cheapest model, scores the whole ``k+1``-token window in ONE jitted
verifier forward (``ServeEngine._verify_jit`` -> ``models.verify_step``,
DESIGN.md §17.1), accepts the longest draft prefix the verifier agrees
with, and falls back to the verifier's own token at the first mismatch —
so the emitted stream is token-exact with greedy decode on the verifier
alone (``accept_spec`` is the pure acceptance rule the property tests
drive).

Two models, one discipline (DESIGN.md §17.2): each model keeps its own
``PlanCache`` with role-tagged keys (draft/verify programs never collide
with plain greedy plans), the draft's dispatcher pins the cheapest
backend while the verifier keeps pallas/offload routing, and both commit
into ONE ``OffloadLedger`` with ``role="draft"``/``"verify"`` tags —
every round's interleaved commits sit inside one ledger span, so the
§16.2 integer-exactness invariant and the by_role split close together.

The acceptance loop is zero-retrace (DESIGN.md §17.3): per round it runs
``k+1`` draft step calls (the extra feed writes d_k's KV entry so a
full-accept rollforward is always cache-consistent), one verify call,
one jitted length splice per model (``model.set_slot_lengths`` — stale
window entries beyond the accepted prefix stay in place, masked then
overwritten), and ONE host sync — against the greedy loop's sync per
token, a second, structural source of the speedup next to the
draft/verifier FLOP gap.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as model_lib
from repro.serve.engine import GenerationResult, ServeEngine
from repro.serve.kvcache import SlotKVPool
from repro.serve.paging import PagedScheduler
from repro.serve.scheduler import ContinuousBatchingScheduler, TokenEvent


def accept_spec(drafts: np.ndarray, vtoks: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The pure greedy-acceptance rule (DESIGN.md §17.1).

    drafts: (B, k) draft proposals d_1..d_k; vtoks: (B, k+1) verifier
    argmaxes over the window [t_0, d_1..d_k] — ``vtoks[:, j]`` is what
    greedy decode on the verifier would emit after consuming the first
    ``j+1`` window tokens. Returns ``(accept_len, committed, n_emit)``:
      accept_len (B,)     longest prefix with drafts[j] == vtoks[j]
      committed (B, k+1)  the emitted tokens — accepted drafts then the
                          verifier's own token at the first mismatch (or
                          its bonus token after a full accept); entries
                          past ``n_emit`` are padding
      n_emit (B,)         accept_len + 1 (every round emits >= 1 token)

    Token-exact by construction: the emitted prefix is precisely what
    feeding the verifier one token at a time would produce, for ANY
    draft/verify pair (tests/test_speculative.py property)."""
    drafts = np.asarray(drafts)
    vtoks = np.asarray(vtoks)
    b, k = drafts.shape
    if vtoks.shape != (b, k + 1):
        raise ValueError(f"vtoks must be (B, k+1); got {vtoks.shape} "
                         f"for drafts {drafts.shape}")
    mismatch = drafts != vtoks[:, :k]
    accept_len = np.where(mismatch.any(axis=1), mismatch.argmax(axis=1),
                          k).astype(np.int64)
    committed = np.concatenate(
        [drafts, np.zeros((b, 1), drafts.dtype)], axis=1)
    rows = np.arange(b)
    committed[rows, accept_len] = vtoks[rows, accept_len]
    return accept_len, committed, accept_len + 1


@functools.partial(jax.jit, donate_argnums=0, keep_unused=True)
def _rollback(state, new_len):
    """Jitted per-slot length splice (DESIGN.md §17.1): one compiled
    program per state structure (verifier + draft), zero retraces across
    rounds — mixed accept lengths are data, not shapes. The state is
    donated, so the lengths update in place and the caller's old state
    must not be read again; ``keep_unused`` keeps the old counters, which
    are overwritten whole, as arguments that can lend their buffers."""
    return model_lib.set_slot_lengths(state, new_len)


@dataclass
class SpeculativeEngine:
    """Two-model speculative decoder (DESIGN.md §17): ``draft`` proposes
    ``k`` tokens per round, ``verifier`` scores the k+1 window in one
    jitted forward, greedy acceptance keeps the output token-exact with
    ``verifier.transcribe()``. Build via ``ServeEngine.speculative()``
    (which pins the draft to the cheapest backend and shares the
    verifier's ledger); constructing directly works when the caller owns
    both engines."""
    verifier: ServeEngine
    draft: ServeEngine
    k: int = 4
    # lifetime counters (the acceptance-rate report, DESIGN.md §17.3)
    rounds: int = 0
    drafted: int = 0
    accepted: int = 0

    def __post_init__(self):
        # validation runs cheapest-first (plain int compares before config
        # inspection), so a multiply-wrong setup surfaces its errors in a
        # fixed, documented order: k, max_len, vocab, family
        # (tests/test_speculative.py parametrizes every guard)
        if self.k < 1:
            raise ValueError("k must be >= 1")
        cap = min(self.verifier.max_len, self.draft.max_len)
        if cap < self.k + 2:
            raise ValueError(
                f"max_len too small for k={self.k}: one round feeds a "
                f"k+1-token window plus the bonus entry, so max_len must "
                f"be >= k + 2 = {self.k + 2} (verifier "
                f"{self.verifier.max_len}, draft {self.draft.max_len})")
        vc, dc = self.verifier.cfg, self.draft.cfg
        if dc.vocab_size != vc.vocab_size:
            raise ValueError(
                f"draft and verifier must share a vocabulary to compare "
                f"tokens: {dc.vocab_size} != {vc.vocab_size}")
        # both models prefill the same payload, and the verifier scores
        # windows
        self.verifier.family.require("verify")
        self.draft.family.require("verify")

    # ------------------------------------------------------------------
    def transcribe(self, mel: np.ndarray, sot_id: int = 1,
                   max_new: int = 32) -> List[GenerationResult]:
        """Speculative twin of ``ServeEngine.transcribe`` — same token
        contract (the generated tokens only, rows truncated at their
        first EOS inclusive), token-exact with the verifier's own greedy
        decode of the same batch."""
        v, d, k = self.verifier, self.draft, self.k
        w = k + 1
        b, f = int(mel.shape[0]), int(mel.shape[1])
        need = max_new + k + 1           # window writes reach pos G + k
        if v.max_len < need or d.max_len < need:
            raise ValueError(
                f"max_len must be >= max_new + k + 1 = {need} "
                f"(verifier {v.max_len}, draft {d.max_len})")
        if v.offload is not None and v.offload.tuner is not None:
            tuner = v.offload.tuner
            n0 = tuner.searches
            from repro.models import whisper as whisper_lib
            whisper_lib.warm_tuning(v.cfg, v.offload, n_frames=f, batch=b,
                                    n_tokens=max_new, quant=v._serve_quant)
            # the verify window's m = B*(k+1) rows per linear
            whisper_lib.warm_tuning(v.cfg, v.offload, n_frames=f,
                                    batch=b * w, n_tokens=max_new,
                                    quant=v._serve_quant)
            if tuner.searches > n0:
                tuner.save()
        mel_j = jnp.asarray(mel)
        tele = v.telemetry

        # plans: prefills are the SAME traced programs as the plain path
        # (plain keys -> shared PlanCache entries); the draft step and the
        # verify window are role-keyed (DESIGN.md §17.2). Both prefills
        # dispatch before either is waited on.
        t0 = time.perf_counter()
        with obs.maybe_span(tele, "spec_prefill", cat="engine", ledger=True,
                            args={"batch": b, "frames": f}):
            v_mem, v_state = v.prefill(mel_j, role="verify")
            d_mem, d_state = d.prefill(mel_j, role="draft")
            jax.block_until_ready(v_mem)
            jax.block_until_ready(d_mem)
            prefill_s = time.perf_counter() - t0

        # per-row accept lengths need per-slot positions: the slot layout
        # (DESIGN.md §11.1) inside a run-to-completion static batch
        v_state = model_lib.slot_layout(v_state, b)
        d_state = model_lib.slot_layout(d_state, b)

        cur = jnp.full((b, 1), sot_id, jnp.int32)
        nodone = jnp.zeros((b,), bool)
        d_step_plan = d.program_plan(b, d_state, f, role="draft")
        v_verify_plan = v.program_plan(b, v_state, f, k=k, role="verify")

        toks: List[List[int]] = [[] for _ in range(b)]
        done = np.zeros(b, bool)
        prev_len = np.zeros(b, np.int64)
        eos = v.eos_id if (v.eos_id is not None and v.eos_id >= 0) else None
        rows = np.arange(b)

        t0 = time.perf_counter()
        while not done.all():
            h = tele.ledger_open("spec_round") if tele is not None else None
            active_mask = ~done
            active = int(active_mask.sum())
            # --- draft k tokens; the k+1-th feed writes d_k's KV entry
            # so a full accept leaves the draft cache consistent
            dtoks = []
            dt = cur
            for _ in range(k):
                dt, _, d_w = d._step_jit(d._serve_params, dt, nodone,
                                         d_state)
                d_state = model_lib.with_step_writes(d_state, d_w)
                dtoks.append(dt)
            _, _, d_w = d._step_jit(d._serve_params, dtoks[-1], nodone,
                                    d_state)
            d_state = model_lib.with_step_writes(d_state, d_w)
            # --- verify the whole window in ONE forward
            window = jnp.concatenate([cur] + dtoks, axis=1)      # (B, k+1)
            vlogits, v_w = v._verify_jit(v._serve_params, window, v_state)
            v_state = model_lib.with_step_writes(v_state, v_w)
            vtoks = v._argmax(vlogits)                           # (B, k+1)
            # --- the round's single host sync
            vt, win = jax.device_get((vtoks, window))
            accept_len, committed, n_emit = accept_spec(win[:, 1:], vt)
            # --- emit + rollback: fed == emitted per row, so the splice
            # target is prev + used; finished rows freeze (used = 0)
            new_len = prev_len.copy()
            for i in range(b):
                if done[i]:
                    continue
                used = 0
                for t in committed[i, :n_emit[i]]:
                    toks[i].append(int(t))
                    used += 1
                    if eos is not None and int(t) == eos:
                        done[i] = True
                        break
                    if len(toks[i]) >= max_new:
                        done[i] = True
                        break
                new_len[i] = prev_len[i] + used
            prev_len = new_len
            nl = jnp.asarray(new_len, jnp.int32)
            v_state = _rollback(v_state, nl)
            d_state = _rollback(d_state, nl)
            cur = jnp.asarray(vt[rows, accept_len][:, None].astype(np.int32))
            # --- accounting: draft + verify commits interleave inside
            # ONE ledger span (the §16.2 exactness the satellite gates)
            self.rounds += 1
            self.drafted += active * k
            self.accepted += int(accept_len[active_mask].sum())
            if d.offload is not None:
                d.offload.ledger.commit(d_step_plan, times=k + 1,
                                        role="draft")
            if v.offload is not None:
                v.offload.ledger.commit(v_verify_plan, times=1,
                                        role="verify")
            if tele is not None:
                tele.ledger_close(h, cat="step",
                                  args={"round": self.rounds,
                                        "active": int(active)})
                tele.inc("repro_spec_rounds_total")
                tele.inc("repro_spec_drafted_total", active * k)
                tele.inc("repro_spec_accepted_total",
                         int(accept_len[active_mask].sum()))
        jax.block_until_ready(cur)
        decode_s = time.perf_counter() - t0
        if tele is not None:
            tele.gauge("repro_spec_acceptance_rate", self.acceptance_rate())
            tele.gauge("repro_spec_verify_traces", v._verify_traces)
        return [GenerationResult(tokens=toks[i], prefill_s=prefill_s / b,
                                 decode_s=decode_s / b, steps=len(toks[i]))
                for i in range(b)]

    # ------------------------------------------------------------------
    # Round-boundary scheduling (DESIGN.md §17.4) — thin factories over
    # the mixin schedulers below; transcribe() stays the one-shot path.
    # ------------------------------------------------------------------
    def continuous(self, n_slots: int = 4,
                   n_frames: Optional[int] = None
                   ) -> "SpecContinuousScheduler":
        """A continuous-batching scheduler that decodes in speculative
        rounds (DESIGN.md §17.4): queued utterances admit into freed wave
        rows at round boundaries — the rollback splice freezes finished
        rows at ``used = 0``, so a round boundary is a safe admission
        point exactly like the §11 between-steps boundary."""
        return SpecContinuousScheduler(self, n_slots=n_slots,
                                      n_frames=n_frames)

    def paged(self, n_slots: int = 4, n_frames: Optional[int] = None,
              **page_cfg) -> "PagedSpecScheduler":
        """Speculative rounds over the §15 paged KV pool: the verify
        window reads/writes through the block tables (multi-entry
        scatter), the pre-round capacity pass allocates any page the
        window will cross into (CoW-first, preempting when the arena is
        dry), and the post-round trim releases pages a rejected suffix
        crossed into."""
        return PagedSpecScheduler(self, n_slots=n_slots, n_frames=n_frames,
                                  **page_cfg)

    def acceptance_rate(self) -> float:
        return self.accepted / max(self.drafted, 1)

    def stats(self) -> Dict[str, Any]:
        """The consolidated speculative report (DESIGN.md §17.3):
        acceptance + the zero-retrace counters + the by_role FLOP split
        from the shared ledger."""
        out = {"k": self.k, "rounds": self.rounds, "drafted": self.drafted,
               "accepted": self.accepted,
               "acceptance_rate": self.acceptance_rate(),
               "verify_traces": self.verifier._verify_traces,
               "draft_step_traces": self.draft._step_traces}
        if self.verifier.offload is not None:
            out["by_role"] = dict(self.verifier.offload.stats.by_role)
        return out


@dataclass
class SpecScheduler:
    """Wave scheduler over a ``SpeculativeEngine`` (DESIGN.md §17.4):
    queued utterances run to completion in fixed-width waves — one
    compiled shape per (wave width, frame count), short waves padded with
    zero-mel rows — so steady-state serving reuses the engine's compiled
    draft/verify programs across waves. Deliberately simpler than the
    continuous-batching scheduler (DESIGN.md §11): run-to-completion
    waves keep the zero-retrace and token-exactness guarantees without a
    slot pool, which makes this the parity REFERENCE the round-boundary
    schedulers below (``SpecContinuousScheduler``/``PagedSpecScheduler``,
    DESIGN.md §17.4) are gated against."""
    engine: SpeculativeEngine
    n_slots: int = 4
    _queue: List[Tuple[int, np.ndarray, int, int]] = field(
        default_factory=list)
    _next_rid: int = 0

    def submit(self, mel: np.ndarray, max_new: int = 32,
               sot_id: int = 1) -> int:
        arr = np.asarray(mel, np.float32)
        if arr.ndim == 2:
            arr = arr[None]
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, arr, max_new, sot_id))
        return rid

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    def run(self) -> Dict[int, GenerationResult]:
        out: Dict[int, GenerationResult] = {}
        while self._queue:
            wave, self._queue = (self._queue[:self.n_slots],
                                 self._queue[self.n_slots:])
            frames = {q[1].shape[1] for q in wave}
            sots = {q[3] for q in wave}
            if len(frames) > 1 or len(sots) > 1:
                raise ValueError(
                    "a wave must share frame count and SOT token "
                    f"(got frames={sorted(frames)}, sot={sorted(sots)})")
            mels = [q[1] for q in wave]
            pad = self.n_slots - len(wave)
            if pad:
                mels.append(np.zeros((pad, *mels[0].shape[1:]), np.float32))
            batch = np.concatenate(mels, axis=0)
            max_new = max(q[2] for q in wave)
            results = self.engine.transcribe(batch, sot_id=wave[0][3],
                                             max_new=max_new)
            for (rid, _, req_max, _), r in zip(wave, results):
                row = r.tokens[:req_max]
                out[rid] = GenerationResult(
                    tokens=row, prefill_s=r.prefill_s,
                    decode_s=r.decode_s, steps=len(row))
        return out


# ---------------------------------------------------------------------------
# Round-boundary continuous/paged scheduling (DESIGN.md §17.4)
# ---------------------------------------------------------------------------
class _SpecRoundsMixin:
    """Speculative rounds over the §11 slot machinery (DESIGN.md §17.4).

    Placed FIRST in the MRO over ``ContinuousBatchingScheduler`` /
    ``PagedScheduler``: the base class keeps the whole queue / evict /
    attribution / telemetry apparatus, and this mixin swaps the per-step
    decode for a speculative ROUND — ``k+1`` draft steps at pool width,
    ONE verify forward over the (n_slots, k+1) window, the pure
    ``accept_spec`` rule, and one rollback splice per model. A round
    boundary is a safe admission point exactly like the §11 between-steps
    boundary: the splice freezes finished rows at ``used = 0``, so a
    freed slot's garbage rows never advance and the next ``admit()`` can
    overwrite them whole.

    The draft model mirrors the verifier's slot pool in a contiguous
    ``SlotKVPool`` whose own free list is never consulted — slot ids ARE
    the verifier pool's slot ids, ``insert()`` writes any row, and a
    row's lifetime is its verifier slot's lifetime. Both models roll back
    through the one shared ``_rollback`` jit, so each keeps one compiled
    splice per state structure.

    Attribution follows §11.3 unchanged: each round's wall time splits
    evenly over the slots active that round, draft admissions (prefill +
    preemption replay) land on the owning request AND the independent
    ``_busy_s`` accumulator, so per-request PDP still sums to the batch
    total. Single-device only: the rollback splice carries no sharded
    out_shardings yet (mesh composition stays with ``SpecScheduler``)."""

    def _init_spec(self, spec: SpeculativeEngine) -> None:
        v, d = spec.verifier, spec.draft
        if v.mesh is not None or d.mesh is not None:
            raise NotImplementedError(
                "speculative round scheduling is single-device: the "
                "rollback splice has no sharded out_shardings — use "
                "SpecScheduler waves on a mesh")
        self.spec = spec
        self._draft_pool = SlotKVPool(d.cfg, d._serve_params, self.n_slots,
                                      d.max_len, n_frames=self.n_frames)
        self._draft_step_plan = None
        self._verify_plan = None

    # -- admission (round boundary == between-steps boundary) -----------
    def submit(self, payload, max_new: int = 32, sot_id: int = 1) -> int:
        spec = self.spec
        need = max_new + spec.k + 1      # window writes reach pos G + k
        cap = min(spec.verifier.max_len, spec.draft.max_len)
        if max_new > 0 and need > cap:
            raise ValueError(
                f"max_len must be >= max_new + k + 1 = {need} "
                f"(verifier {spec.verifier.max_len}, draft "
                f"{spec.draft.max_len})")
        return super().submit(payload, max_new=max_new, sot_id=sot_id)

    def admit(self) -> List[int]:
        # snapshot the queue before the base admit pops it: the draft's
        # mirror admission needs each request's payload + SOT
        pend = {q.rid: q for q in self.queue}
        admitted = super().admit()
        if admitted:
            by_rid = {a.rid: slot for slot, a in self._active.items()}
            for rid in admitted:
                self._admit_draft(by_rid[rid], pend[rid])
        return admitted

    def _admit_draft(self, slot: int, req) -> None:
        """Mirror one admission into the draft pool: a batch-1 prefill,
        plus the deterministic replay of already-streamed tokens when the
        request was preempted mid-flight. Afterwards the draft row holds
        KV for ``[SOT, e_0..e_{L-2}]`` at length L with pending token
        ``e_{L-1}`` — the same invariant every speculative round
        maintains on the verifier slot, so drafting resumes seamlessly."""
        d = self.spec.draft
        tele = self.telemetry
        a = self._active[slot]
        tokens = list(a.tokens)          # non-empty only after preemption
        payload = jnp.asarray(req.payload)
        # the ledger span tightly scopes the draft-side prefill + replay
        # exec and commits, preserving §16.2 span exactness (the draft
        # shares the verifier's ledger, so unclaimed commits here would
        # break ledger_consistent on the serving telemetry)
        with obs.maybe_span(tele, "spec_draft_admit", cat="lifecycle",
                            track=obs.request_track(a.rid), rid=a.rid,
                            ledger=True):
            t0 = time.perf_counter()
            _, state = d.prefill(payload, role="draft")
            if tokens:
                state = d.replay(state, [req.sot_id] + tokens[:-1],
                                 self.n_frames, role="draft")
            state = jax.block_until_ready(state)
            wall = time.perf_counter() - t0
        self._busy_s += wall
        a.prefill_s += wall
        self._draft_pool.insert(slot, state)
        if tele is not None:
            tele.instant("spec_admit", rid=a.rid, slot=slot,
                         replayed=len(tokens))
            tele.inc("repro_spec_admissions_total")

    # -- layout hooks (overridden by the paged subclass) ----------------
    def _pre_round(self, w: int) -> None:
        """Capacity hook before the round's W writes — a no-op on the
        contiguous pool (slots own max_len up front)."""

    def _evict_slot(self, slot: int, rid: int) -> None:
        self.pool.release(slot, reset=False)

    def _post_round(self, new_len: np.ndarray) -> None:
        """Rollback hook after the length splice — a no-op on the
        contiguous pool (stale window entries just get overwritten)."""

    # -- the speculative round ------------------------------------------
    def _ensure_step_plan(self) -> None:
        if self._step_plan_ready:
            return
        spec = self.spec
        self._draft_step_plan = spec.draft.program_plan(
            self.n_slots, self._draft_pool.state, self.n_frames,
            role="draft")
        self._verify_plan = spec.verifier.program_plan(
            self.n_slots, self.pool.state, self.n_frames, k=spec.k,
            pages=self.pool.plan_geometry, role="verify")
        self._step_plan_ready = True

    def decode_step(self) -> List[TokenEvent]:
        """One speculative round at pool width. Emits up to ``k+1``
        ``TokenEvent``s per active slot (each request's event stream
        stays ordered by its per-request ``step``); finished requests
        evict exactly as in the base scheduler, and their rows freeze at
        length 0 through the rollback splice."""
        if not self._active:
            return []
        spec = self.spec
        v, d, k = spec.verifier, spec.draft, spec.k
        self._pre_round(k + 1)
        if not self._active:             # capacity pass preempted them all
            return []
        self._ensure_step_plan()
        self._note_kv_usage()
        tele = self.telemetry
        if tele is not None:
            h = tele.ledger_open("spec_round")
        t0 = time.perf_counter()
        dpool = self._draft_pool
        d_state = dpool.state
        # k draft steps; the k+1-th feed writes d_k's KV entry so a full
        # accept leaves the draft cache consistent (DESIGN.md §17.1)
        dt = self._tokens
        dtoks = []
        for _ in range(k):
            dt, _, d_w = d._step_jit(d._serve_params, dt, self._done0,
                                     d_state)
            d_state = model_lib.with_step_writes(d_state, d_w)
            dtoks.append(dt)
        _, _, d_w = d._step_jit(d._serve_params, dtoks[-1], self._done0,
                                d_state)
        dpool.state = model_lib.with_step_writes(d_state, d_w)
        # ONE verify forward over the whole window, then the round's
        # single host sync
        window = jnp.concatenate([self._tokens] + dtoks, axis=1)
        vlogits, v_w = v._verify_jit(v._serve_params, window,
                                     self.pool.state)
        self.pool.state = model_lib.with_step_writes(self.pool.state, v_w)
        vtoks = v._argmax(vlogits)
        vt, win = jax.device_get((vtoks, window))
        dt_s = time.perf_counter() - t0
        self._busy_s += dt_s
        if d.offload is not None:
            d.offload.ledger.commit(self._draft_step_plan, times=k + 1,
                                    role="draft")
        if v.offload is not None:
            v.offload.ledger.commit(self._verify_plan, times=1,
                                    role="verify")
        if tele is not None:
            tele.ledger_close(h, cat="step",
                              args={"active": len(self._active)})
        accept_len, committed, n_emit = accept_spec(win[:, 1:], vt)
        share = dt_s / len(self._active)
        now = time.perf_counter()
        eos = v.eos_id
        events: List[TokenEvent] = []
        new_len = np.zeros(self.n_slots, np.int64)
        pending = np.zeros(self.n_slots, np.int64)
        drafted = len(self._active) * k
        accepted = 0
        for slot in sorted(self._active):
            a = self._active[slot]
            a.decode_s += share
            accepted += int(accept_len[slot])
            done = False
            for t in committed[slot, :n_emit[slot]]:
                tok = int(t)
                a.tokens.append(tok)
                a.steps += 1
                if a.steps == 1 and a.ttft_s == 0.0 and a.submit_t > 0.0:
                    a.ttft_s = now - a.submit_t
                    if tele is not None:
                        self._buf_ttft.append(a.ttft_s)
                done = (a.steps >= a.max_new
                        or (eos is not None and tok == eos))
                events.append(TokenEvent(a.rid, tok, a.steps, done))
                if done:
                    break
            # fed == emitted per row: the splice target is the emitted
            # count, and the next round's feed is the last emitted token
            # (== the verifier's token at the mismatch/bonus position)
            new_len[slot] = a.steps
            pending[slot] = a.tokens[-1]
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
                self._evict_slot(slot, a.rid)
                new_len[slot] = 0        # freeze the freed row
                pending[slot] = 0
        nl = jnp.asarray(new_len, jnp.int32)
        self.pool.state = _rollback(self.pool.state, nl)
        dpool.state = _rollback(dpool.state, nl)
        self._post_round(new_len)
        self._tokens = jnp.asarray(pending[:, None].astype(np.int32))
        spec.rounds += 1
        spec.drafted += drafted
        spec.accepted += accepted
        if tele is not None:
            self._buf_tokens += len(events)
            self._buf_steps.append(dt_s)
            tele.inc("repro_spec_rounds_total")
            tele.inc("repro_spec_drafted_total", drafted)
            tele.inc("repro_spec_accepted_total", accepted)
            g = (len(self.queue), len(self._active), v._verify_traces,
                 self.kv_used_peak)
            if g != self._gauge_state:
                self._gauge_state = g
                gq, gs, gt, gu = self._step_gauges
                gq.set(g[0])
                gs.set(g[1])
                gt.set(g[2])
                gu.set(self.kv_utilization_peak)
        return events


class SpecContinuousScheduler(_SpecRoundsMixin, ContinuousBatchingScheduler):
    """Continuous batching in speculative rounds over the contiguous slot
    pool (DESIGN.md §17.4) — build via ``SpeculativeEngine.continuous()``."""

    def __init__(self, spec: SpeculativeEngine, n_slots: int = 4,
                 n_frames: Optional[int] = None):
        super().__init__(spec.verifier, n_slots=n_slots, n_frames=n_frames)
        self._init_spec(spec)


class PagedSpecScheduler(_SpecRoundsMixin, PagedScheduler):
    """Speculative rounds over the §15 paged KV pool — build via
    ``SpeculativeEngine.paged()``. Three paged-specific moves per round:
    the pre-round capacity pass ensures private pages for all ``k+1``
    window positions (a window may straddle a page boundary — the
    crossing page allocates here, preempting the cheapest victim when the
    arena is dry), the verify window scatters through the block tables
    (``attention.paged_window_update``), and the post-round trim releases
    any page the REJECTED suffix crossed into, so arena accounting is
    exact after every round. The draft side stays contiguous: drafts are
    the cheap model, whose whole pool is smaller than one verifier arena;
    preempted requests replay into BOTH models on re-admission."""

    def __init__(self, spec: SpeculativeEngine, n_slots: int = 4,
                 n_frames: Optional[int] = None, **page_cfg):
        super().__init__(spec.verifier, n_slots=n_slots, n_frames=n_frames,
                         **page_cfg)
        self._init_spec(spec)

    def _pre_round(self, w: int) -> None:
        self._page_capacity_pass(w)
        self.pool.sync()

    def _evict_slot(self, slot: int, rid: int) -> None:
        self.pool.release(slot, reset=False)
        self._payloads.pop(rid, None)

    def _post_round(self, new_len: np.ndarray) -> None:
        # release pages the rejected suffix crossed into: after the
        # splice, pages whose first position sits at/past the new length
        # hold only dead entries (DESIGN.md §17.4)
        pool = self.pool
        released = 0
        for slot in sorted(self._active):
            keep = max(-(-int(new_len[slot]) // pool.page_size), 1)
            released += pool.trim_self_pages(slot, keep)
        if released and self.telemetry is not None:
            self.telemetry.instant("spec_trim", pages=released)
            self.telemetry.inc("repro_spec_pages_trimmed_total", released)
