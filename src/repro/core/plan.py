"""Trace-time dispatch planning — the *plan* half of the plan/ledger split
(DESIGN.md §10).

The paper (and its CGLA companions) resolve per-``ggml_mul_mat`` routing as
a **static, shape-keyed decision** fixed before execution: a kernel either
fits the local-memory budget or it does not, and the burst/tiling operating
point is chosen offline. This module is that idea restated for a traced
JAX program: every routing input — the offload decision, the burst split,
the tuned tiling — is a pure function of *static shapes* plus engine
configuration, so it can be resolved once at trace time and recorded as a
``PlanEntry``. Execution (``core/offload.py OffloadEngine.linear``) then
consumes the entry without any Python-side mutation, which is what lets
the serving decode step sit inside ``jax.jit`` with an engine attached
(DESIGN.md §10.1).

Accounting moves to the other half of the split: a ``DispatchPlan`` knows
the per-execution cost of the traced program (its entries), and the
host-side ``OffloadLedger`` (core/offload.py) multiplies that by how many
times the compiled program actually ran (DESIGN.md §10.2). The in-trace
counter mutation this replaces both broke jit purity and silently
under-counted under any compilation cache.

Plan construction is deterministic: ``plan_linear`` twice with the same
shapes, budget and tuner cache state yields equal entries
(tests/test_plan.py), mirroring §9.2's deterministic analytic cost model.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

from repro.backends import MAIN, KernelRequest, REGISTRY
from repro.core.coverage import MulMat, fits
from repro.core.mixed_exec import select_burst, split_aligned
from repro.sharding.rules import mesh_signature
from repro.tuning import kernel_for, padded_m


@dataclass(frozen=True)
class PlanEntry:
    """Routing record for one linear call site at one static shape.

    Everything the execution path needs (and everything the ledger
    accounts) is here: the ``(name, m, k, n, dtype)`` identity, the
    offload decision, the burst split, the tuned tiling for the main
    segment (``None`` when untuned — execution then falls back to the
    module-level default tiles, exactly as before the refactor), and the
    resolved execution ``backend`` (DESIGN.md §12.3) — a recorded plan
    pins its backend, so the ledger attributes work per backend and
    execution never re-decides what planning decided.
    """
    name: str
    m: int
    k: int
    n: int
    dtype: str                 # "q8_0" | "bf16"
    offload: bool
    burst: int
    tuned: bool
    kernel: str                # kernel the main segment dispatches to
    tiling: Optional[Tuple[int, int, int]]   # (block_m, block_n, block_k)
    k_main: int
    k_res: int
    backend: str = "xla_ref"   # registry backend pinned for the main segment
    # mesh signature the program was planned under (DESIGN.md §13) — None
    # for unsharded programs, so sharded/unsharded entries (and therefore
    # plan signatures) can never compare equal at the same shapes, and the
    # ledger can split per-device attribution exactly
    mesh: Optional[Tuple[Tuple[str, int], ...]] = None

    @property
    def flops(self) -> int:
        return 2 * self.m * self.k * self.n

    @property
    def offloaded_flops(self) -> int:
        """FLOPs on the accelerator kernel (main segment) if offloaded."""
        return self.flops * self.k_main // max(self.k, 1) if self.offload else 0

    @property
    def residual_flops(self) -> int:
        return self.flops * self.k_res // max(self.k, 1) if self.offload else 0

    @property
    def fallback_flops(self) -> int:
        return 0 if self.offload else self.flops


def plan_linear(name: str, m: int, k: int, n: int, *, quantized: bool,
                vmem_budget_kb: int, default_burst: int,
                tuner=None, backend: Optional[str] = None,
                mesh_sig=None) -> PlanEntry:
    """Resolve one linear's routing from static shapes — pure apart from
    tuner-cache warming (a miss runs one search whose winner is cached, so
    repeat calls are deterministic dict hits; see §9.3).

    This is the single source of truth for dispatch: ``OffloadEngine``
    calls it both when recording a plan (trace time) and when executing
    eagerly, so plan and execution can never disagree. ``backend``
    optionally pins the main-segment backend (the engine's legacy
    ``prefer_pallas`` translation); the *resolved* registry backend —
    after ``REPRO_BACKEND`` forcing and capability resolution
    (DESIGN.md §12.2) — is recorded in the entry.
    """
    dtype = "q8_0" if quantized else "bf16"
    kern = kernel_for(m, quantized)
    mp = padded_m(m)
    burst = default_burst
    tuned = False
    if tuner is not None:
        b = select_burst(k, tuner, kernel=kern, m=mp, n=n, dtype=dtype,
                         default=0)
        if b:
            burst, tuned = b, True
    k_main, k_res = split_aligned(k, burst)
    offload = fits(MulMat(name, m=m, k=k, n=n), vmem_budget_kb,
                   optimized=True, agg_units=1)
    tiling = None
    if tuner is not None and offload and k_main:
        # the main segment is what the kernel sees (the executor slices x
        # to k_main before dispatch), so the tiling key uses k_main, not k
        rec = tuner.best_tiling(kern, mp, n, k_main, dtype)
        if rec is not None:
            tiling = (rec.block_m, rec.block_n, rec.block_k)
    # resolve the main-segment backend at plan time (DESIGN.md §12.3): a
    # fallback entry runs the always-available reference path (the old
    # prefer_pallas=False branch of OffloadEngine.execute) — a structural
    # decision (forceable=False), so REPRO_BACKEND cannot push work the
    # coverage model kept off the accelerator back onto it
    if k_main:
        req = KernelRequest(kernel=kern, m=m, n=n, k=k_main, dtype=dtype,
                            segment=MAIN, tiling=tiling, forceable=offload)
        resolved = REGISTRY.resolve(req,
                                    pin=backend if offload else "xla_ref").name
    else:
        # k < burst: there is no main segment — the whole linear runs on
        # the host residual arm, so that is what the entry (and the
        # ledger's by_backend attribution) must name
        resolved = "host_residual"
    return PlanEntry(name=name, m=m, k=k, n=n, dtype=dtype, offload=offload,
                     burst=burst, tuned=tuned, kernel=kern, tiling=tiling,
                     k_main=k_main, k_res=k_res, backend=resolved,
                     mesh=mesh_sig)


@dataclass
class DispatchPlan:
    """The routing of one traced program: ``PlanEntry`` per linear call, in
    trace order. One plan describes ONE execution of the compiled program;
    the ledger multiplies by the run count (DESIGN.md §10.2)."""
    key: Hashable = None
    entries: List[PlanEntry] = field(default_factory=list)
    _counts: Optional[Dict[PlanEntry, int]] = field(
        default=None, repr=False, compare=False)

    def add(self, entry: PlanEntry) -> None:
        self.entries.append(entry)
        self._counts = None

    def counts(self) -> Dict[PlanEntry, int]:
        """Each distinct entry and how often it runs per execution: a
        layer stack's entries repeat once per layer (DESIGN.md §10.2), and
        the ledger commits each distinct entry once, times its count."""
        if self._counts is None:
            self._counts = {}
            for e in self.entries:
                self._counts[e] = self._counts.get(e, 0) + 1
        return self._counts

    def __iter__(self):
        return iter(self.entries)

    def __len__(self) -> int:
        return len(self.entries)

    def signature(self) -> Tuple[PlanEntry, ...]:
        """Hashable identity — equal signatures mean identical routing
        (the determinism contract of tests/test_plan.py)."""
        return tuple(self.entries)

    def summary(self) -> Dict[str, Any]:
        off = [e for e in self.entries if e.offload]
        return {
            "calls": len(self.entries),
            "offloaded": len(off),
            "tuned": sum(1 for e in off if e.tuned),
            "offloaded_flops": sum(e.offloaded_flops for e in self.entries),
            "fallback_flops": sum(e.fallback_flops for e in self.entries),
            "residual_flops": sum(e.residual_flops for e in self.entries),
        }


def plan_key(phase: str, quant: Optional[str], batch: int,
             *extra: Hashable, mesh=None,
             pages: Optional[Tuple[Hashable, ...]] = None,
             role: Optional[str] = None,
             k: Optional[int] = None) -> Tuple[Hashable, ...]:
    """Canonical plan-cache key: ``(phase, quant, batch, *extra)``.

    One key family serves both serving modes (DESIGN.md §11.3): a
    slot-batched continuous-batching step at pool width ``B`` and frame
    capacity ``F`` is the *same* traced program as a static-batch decode
    step at ``(B, F)`` — routing depends only on static shapes — so the
    scheduler (serve/scheduler.py) and the one-shot ``transcribe``/
    ``generate`` paths build identical keys and share ``PlanCache``
    entries instead of re-recording.

    ``mesh`` (a ``Mesh``/``AbstractMesh``, or an already-built
    ``mesh_signature`` tuple) appends the sharding signature
    (DESIGN.md §13): the sharded decode step at ``(B, F)`` is a
    *different* compiled program from its unsharded twin — different
    layouts, different collectives — so they must never share a cache
    entry. ``mesh=None`` leaves pre-mesh keys byte-identical.

    ``pages`` appends the paged-pool geometry (DESIGN.md §15): a paged
    decode step gathers its KV through block tables — a different traced
    program from the contiguous step at the same (batch, frames) — so
    paged and contiguous programs must never share a ``PlanCache`` entry.
    ``pages=None`` leaves contiguous keys byte-identical.

    ``role``/``k`` append the speculative-decoding identity
    (DESIGN.md §17.2): a two-model engine runs a *draft* program and a
    *verify* program whose ``k``-position window makes it a different
    traced program (m = B·(k+1) per linear) from the plain step at the
    same batch — draft, verify and greedy plans must never share a
    ``PlanCache`` entry, and the role tag is what the ledger's
    per-role FLOP attribution keys commits by. ``role=None``/``k=None``
    leave single-model keys byte-identical.

    The qualifiers compose (DESIGN.md §17.4): a paged speculative verify
    window keys ``(..., ("pages", geom), ("role", "verify"), ("k", k))``
    — paged x role x k programs all land in disjoint entries, so the
    round-boundary schedulers (serve/speculative.py) never reuse a
    contiguous or plain-greedy plan for a paged window."""
    base = (phase, quant, batch, *extra)
    sig = mesh_signature(mesh) if hasattr(mesh, "axis_names") else mesh
    if sig is not None:
        base = (*base, ("mesh", sig))
    if pages is not None:
        base = (*base, ("pages", tuple(pages)))
    if role is not None:
        base = (*base, ("role", role))
    if k is not None:
        base = (*base, ("k", k))
    return base


@dataclass
class PlanCache:
    """Plans keyed by ``plan_key``-built ``(phase, quant, batch, ...)``
    tuples so steady-state serving resolves routing with one dict hit and
    zero re-tracing (DESIGN.md §10.3)."""
    plans: Dict[Hashable, DispatchPlan] = field(default_factory=dict)
    hits: int = 0
    misses: int = 0

    def get_or_build(self, key: Hashable,
                     build: Callable[[], DispatchPlan]) -> DispatchPlan:
        plan = self.plans.get(key)
        if plan is not None:
            self.hits += 1
            return plan
        self.misses += 1
        plan = build()
        plan.key = key
        self.plans[key] = plan
        return plan

    def __len__(self) -> int:
        return len(self.plans)


def record_plan(engine, fn, *args, key: Hashable = None) -> DispatchPlan:
    """Build the ``DispatchPlan`` of ``fn(*args)`` by abstractly tracing it
    (``jax.eval_shape`` — shapes only, nothing executes) with the engine in
    recording mode. The recorded entries are exactly what a ``jax.jit`` of
    the same function resolves at its own trace time, because both go
    through ``plan_linear``; planning also warms the tuner cache so the
    real compile's lookups are pure dict hits."""
    import jax

    plan = DispatchPlan(key=key)
    with engine.recording(plan):
        # a fresh wrapper per recording: jax.eval_shape is backed by the
        # jit tracing cache, and a cache hit would skip the trace (and with
        # it the recording side channel) for a repeated (fn, shapes) pair
        jax.eval_shape(lambda *a: fn(*a), *args)
    return plan
