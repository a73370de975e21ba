"""Offload dispatcher — the paper's co-design loop as a runtime feature.

Given a linear layer's shapes and the configured VMEM budget + burst, decide
per-invocation (like IMAX's per-``ggml_mul_mat`` decision) whether the main
segment runs on the accelerator kernel or falls back to the host/XLA path,
and account the PDP consequences. This is the glue between:

  coverage.py  (does the working set fit the local-memory budget?)
  bursts.py    (which granularity minimizes the PDP proxy?)
  backends/    (the execution-backend registry + mixed-split executor —
                the actual compute paths, DESIGN.md §12)
  energy.py    (PDP/EDP accounting per step)
  plan.py      (trace-time routing resolution — DESIGN.md §10)

Plan/ledger split (DESIGN.md §10): ``linear`` is a pure function of its
arguments — routing comes from ``core.plan.plan_linear`` (static shapes
only) and no counters mutate inside a traced call, so the whole decode
step jits with an engine attached. Accounting lives in the host-side
``OffloadLedger``: eager (concrete-input) calls account directly, traced
programs are accounted by committing their recorded ``DispatchPlan``
multiplied by the number of executions (serve/engine.py does this per
request).
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

from repro.backends import executor, pin_for_prefer
from repro.core.coverage import MulMat, fits
from repro.core.plan import DispatchPlan, PlanEntry, plan_linear
from repro.core.qformats import QTensor
from repro.tuning import Autotuner


@dataclass
class OffloadStats:
    """Aggregated accounting (feeds the Fig 12 exec-breakdown benchmark).
    Totals container of the ``OffloadLedger`` — populated from committed
    plans and eager calls, never from inside a traced function."""
    offloaded_calls: int = 0
    fallback_calls: int = 0
    offloaded_flops: int = 0
    fallback_flops: int = 0
    residual_flops: int = 0
    tuned_calls: int = 0        # offloads that ran on a tuned burst
    by_kernel: Dict[str, int] = field(default_factory=dict)
    by_backend: Dict[str, int] = field(default_factory=dict)  # DESIGN.md §12.3
    # per-device FLOP attribution under sharded serving (DESIGN.md §13):
    # slot-DP splits every linear's batch rows evenly across the mesh, so
    # each device's share is flops/n_devices (remainder bookkept to dev0);
    # unsharded entries attribute everything to dev0. The invariant —
    # sum(by_device) == offloaded + fallback + residual flops — is what
    # keeps PDP accounting exact under sharding (gated by
    # benchmarks/sharded_serving.py).
    by_device: Dict[str, int] = field(default_factory=dict)
    # per-role FLOP attribution for multi-model engines (DESIGN.md §17.2):
    # a speculative engine's draft and verifier commit into ONE ledger,
    # tagged "draft"/"verify"; single-model commits (and eager calls)
    # default to "main". Invariant, same shape as by_device:
    # sum(by_role) == offloaded + fallback + residual flops — gated by
    # benchmarks/speculative.py next to the §16.2 span exactness.
    by_role: Dict[str, int] = field(default_factory=dict)

    def offload_rate(self) -> float:
        t = self.offloaded_calls + self.fallback_calls
        return self.offloaded_calls / t if t else 0.0

    def offload_flop_rate(self) -> float:
        t = self.offloaded_flops + self.fallback_flops
        return self.offloaded_flops / t if t else 0.0


@dataclass
class OffloadLedger:
    """Host-side accounting — the *ledger* half of the plan/ledger split
    (DESIGN.md §10.2). One entry-accounting path serves both modes: eager
    calls account their entry once; jitted programs commit their recorded
    ``DispatchPlan`` times the number of executions, which reproduces
    exactly the totals the old in-trace counters produced when every call
    ran un-jitted (tests/test_plan.py asserts this equivalence)."""
    totals: OffloadStats = field(default_factory=OffloadStats)
    commits: int = 0            # plans committed (not executions)

    def account(self, entry: PlanEntry, times: int = 1,
                role: str = "main") -> None:
        s = self.totals
        if entry.offload:
            s.offloaded_calls += times
            if entry.tuned:
                s.tuned_calls += times
            s.offloaded_flops += entry.offloaded_flops * times
            s.residual_flops += entry.residual_flops * times
        else:
            s.fallback_calls += times
            s.fallback_flops += entry.fallback_flops * times
        s.by_kernel[entry.name] = s.by_kernel.get(entry.name, 0) + times
        s.by_backend[entry.backend] = (s.by_backend.get(entry.backend, 0)
                                       + times)
        # per-device split (DESIGN.md §13): entry.flops covers the whole
        # linear (main + residual when offloaded, fallback otherwise), so
        # the even split keeps sum(by_device) equal to the flop totals
        n_dev = 1
        for _, size in (entry.mesh or ()):
            n_dev *= int(size)
        share, rem = divmod(entry.flops * times, n_dev)
        for i in range(n_dev):
            dev = f"dev{i}"
            s.by_device[dev] = (s.by_device.get(dev, 0) + share
                                + (rem if i == 0 else 0))
        # per-role split (DESIGN.md §17.2): whole-linear flops, so
        # sum(by_role) stays equal to the flop totals like by_device
        s.by_role[role] = s.by_role.get(role, 0) + entry.flops * times

    def commit(self, plan: Optional[DispatchPlan], times: int = 1,
               role: str = "main") -> None:
        """Account ``times`` executions of a traced program's plan.
        ``role`` tags the commit for multi-model attribution
        (DESIGN.md §17.2) — "draft"/"verify" from a speculative engine,
        "main" everywhere else."""
        if plan is None or times <= 0:
            return
        for entry, n in plan.counts().items():
            self.account(entry, times * n, role=role)
        self.commits += 1


@dataclass
class OffloadEngine:
    """The dispatcher. ``vmem_budget_kb`` is the LMM-size analog (per-core
    VMEM claim allowed for one invocation's working set; agg_units=1 on TPU);
    ``burst`` is the lane granularity from the burst sweep — the *untuned*
    fallback when no ``tuner`` is attached. With a ``tuner``
    (tuning.Autotuner), both the split granularity and the kernel tile
    shapes come from the persistent tuning cache (DESIGN.md §9.4): a cache
    hit is a dict lookup, so steady-state dispatch stays cheap — and with
    the plan/ledger split (DESIGN.md §10) even that lookup happens only at
    trace time; compiled steady-state dispatch is zero Python."""
    vmem_budget_kb: int = 8 * 1024      # half of v5e's ~16 MiB VMEM
    burst: int = 256
    prefer_pallas: Optional[bool] = None
    interpret: Optional[bool] = None
    tuner: Optional[Autotuner] = None
    ledger: OffloadLedger = field(default_factory=OffloadLedger)
    # mesh signature of the serving mesh this engine dispatches under
    # (DESIGN.md §13) — set by ServeEngine when a mesh is attached; stamped
    # into every PlanEntry so sharded plans never compare equal to
    # unsharded ones and the ledger can attribute work per device
    mesh_sig: Optional[tuple] = None
    _recording: Optional[DispatchPlan] = field(default=None, repr=False)
    # executions per program run of each linear traced now (``repeat``)
    _repeat: int = field(default=1, repr=False)

    @property
    def stats(self) -> OffloadStats:
        """Ledger totals — same read API as the pre-§10 in-trace counters."""
        return self.ledger.totals

    def should_offload(self, m: int, k: int, n: int, name: str = "linear") -> bool:
        mm = MulMat(name, m=m, k=k, n=n)
        return fits(mm, self.vmem_budget_kb, optimized=True, agg_units=1)

    # -- planning ---------------------------------------------------------
    def plan_entry(self, m: int, k: int, n: int, *, quantized: bool,
                   name: str = "linear") -> PlanEntry:
        """Resolve routing for one static shape (pure; DESIGN.md §10.1).
        The entry pins the registry backend (DESIGN.md §12.3), translated
        from this engine's legacy ``prefer_pallas`` tri-state."""
        return plan_linear(name, m, k, n, quantized=quantized,
                           vmem_budget_kb=self.vmem_budget_kb,
                           default_burst=self.burst, tuner=self.tuner,
                           backend=pin_for_prefer(self.prefer_pallas),
                           mesh_sig=self.mesh_sig)

    @contextmanager
    def recording(self, plan: DispatchPlan):
        """While active, every ``linear`` call appends its ``PlanEntry`` to
        ``plan`` instead of accounting to the ledger — used under abstract
        tracing (``plan.record_plan``) to capture a program's routing."""
        prev, self._recording = self._recording, plan
        try:
            yield plan
        finally:
            self._recording = prev

    @contextmanager
    def repeat(self, n: int):
        """While active, each ``linear`` traced runs ``n`` times per
        execution of the program: the body of a ``lax.scan``, or a
        ``vmap``, over ``n`` stacked layers is traced once but executes
        per layer, so it records (or accounts) ``n`` entries, not one
        (DESIGN.md §10.2). Nests multiplicatively."""
        prev, self._repeat = self._repeat, self._repeat * n
        try:
            yield
        finally:
            self._repeat = prev

    # -- execution --------------------------------------------------------
    def linear(self, x: jax.Array, w, name: str = "linear") -> jax.Array:
        """y = x @ W^T, routed per the trace-time plan entry for this
        shape. Pure under tracing: the entry derives from static shapes,
        the kernel call is functional, and accounting only happens on
        concrete (eager) inputs or into an explicit recording plan —
        never as a side effect inside someone else's ``jax.jit`` trace."""
        k = x.shape[-1]
        n = w.shape[0]
        m = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        entry = self.plan_entry(m, k, n, quantized=isinstance(w, QTensor),
                                name=name)
        y = self.execute(x, w, entry)
        n = self._repeat
        if self._recording is not None:
            for _ in range(n):
                self._recording.add(entry)
        elif not isinstance(x, jax.core.Tracer):
            self.ledger.account(entry, times=n)
            # eager accounts land outside any ledger span; claiming them
            # on the active telemetry keeps the DESIGN.md §16.2 exact
            # span-FLOP == ledger-delta invariant under mixed usage
            from repro import obs
            tele = obs.active()
            if tele is not None and tele._ledger is self.ledger:
                tele.claim_eager(entry, times=n)
        return y

    def execute(self, x: jax.Array, w, entry: PlanEntry) -> jax.Array:
        """Run one linear per a resolved ``PlanEntry`` — a pure function of
        ``(x, w, entry)`` plus engine path config (DESIGN.md §10.1). The
        entry pins burst, tiling AND backend; ``registry.dispatch`` (via
        the executor) is the only place a kernel implementation is
        selected — no backend conditionals here (DESIGN.md §12.3)."""
        return executor.matmul(x, w, burst=entry.burst,
                               backend=entry.backend, tiling=entry.tiling,
                               interpret=self.interpret,
                               forceable=entry.offload)
