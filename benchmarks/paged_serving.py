"""Paged vs contiguous KV serving under a shared-prefix Poisson workload
(DESIGN.md §15; the memory-capacity analog of the paper's §5.1 sustained
multi-utterance evaluation).

The contiguous slot pool commits ``n_slots x (max_len + n_frames)`` KV up
front, so concurrency is capped by committed bytes even when utterances
repeat a hot audio preamble and budgets stay far below ``max_len``. The
paged pool (serve/paging.py) sizes ONE page arena to the workload,
deduplicates identical utterances' cross-KV by content hash, and
oversubscribes logical slots against physical pages with
preempt-and-recompute — so the same memory admits more concurrent
requests.

Both schedulers replay the SAME deterministic arrival trace (Poisson
gaps in decode-step units — the virtual clock advances one unit per
batch step, so the release schedule is machine-independent), for dense
bf16 AND q8_0+offload. Gates, asserted every run (CI via ``--smoke``):

  - token-exact parity: every request's paged token stream equals its
    contiguous stream (greedy decode rows are independent, so this holds
    through sharing, oversubscription, and preemption)
  - zero step retraces: ONE ``step_fn`` trace per engine across the
    whole schedule (replays ride the batch-1 ``_decode_jit``, which by
    design never touches the step trace counter)
  - >=2x admitted-requests-per-GB: peak concurrent admissions per
    committed KV byte, paged vs contiguous
  - preemption correctness: a deliberately tight arena (forcing
    preempt-and-recompute) still reproduces the contiguous token streams

Committed-KV bytes and peak utilization are reported next to tok/s and
p50/p95/p99 for every mode (DESIGN.md §15.4); the percentiles come from
the shared ``obs.metrics`` histogram in exact (track_values) mode.

Telemetry (DESIGN.md §16) rides the q8_0+offload variant's paged AND
tight-arena engines, adding gates: every lifecycle span closes through
prefix hits, CoW splits, preemptions and replays; span nesting holds;
and the sum of ledger-span FLOP deltas equals the ledger total EXACTLY
(§16.2). ``--trace-out``/``--metrics-out`` export the paged engine's
trace (Perfetto trace_event JSON, validated by tools/check_trace.py in
CI) and metrics exposition.

Usage:
  PYTHONPATH=src python -m benchmarks.paged_serving [--smoke]
      [--trace-out PATH] [--metrics-out PATH]

Writes experiments/bench/paged_serving.json.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import fmt_table, save
from repro import obs
from repro.configs.registry import get_config, get_smoke_config
from repro.core.offload import OffloadEngine
from repro.models import model as model_lib
from repro.obs.metrics import LATENCY_BUCKETS_S, Histogram
from repro.serve.engine import ServeEngine


def _latency_summary(xs: List[float]) -> Dict[str, float]:
    """p50/p95/p99 (step units) through the ONE shared percentile
    implementation (repro.obs.metrics, DESIGN.md §16.3), exact mode."""
    h = Histogram("latency_steps", LATENCY_BUCKETS_S, track_values=True)
    for x in xs:
        h.observe(x)
    return {"p50_steps": h.percentile(50), "p95_steps": h.percentile(95),
            "p99_steps": h.percentile(99)}


def _drive(sched, mels: List[np.ndarray], max_news: List[int],
           arrivals: np.ndarray) -> Dict[str, object]:
    """Replay the arrival trace on a virtual step clock (one unit per
    batch decode step — deterministic across machines and modes), driving
    admit/decode manually so results stay in ``finished`` for the
    attribution check. Returns per-request token streams in submit order,
    step-unit latencies, and real wall-clock throughput."""
    t, i, n = 0, 0, len(mels)
    rid2idx: Dict[int, int] = {}
    done_at: Dict[int, int] = {}
    wall0 = time.perf_counter()
    while i < n or sched.n_queued or sched.n_active:
        while i < n and arrivals[i] <= t:
            rid2idx[sched.submit(mels[i], max_new=max_news[i])] = i
            i += 1
        sched.admit()
        if sched.n_active:
            for ev in sched.decode_step():
                if ev.done:
                    done_at[rid2idx[ev.rid]] = t + 1
            t += 1
        elif i < n:
            t = int(arrivals[i])          # idle: jump to the next arrival
    wall = time.perf_counter() - wall0
    att = sched.attribution()
    per_req = sum(att["per_request_pdp_j"].values())
    assert abs(per_req - att["batch_pdp_j"]) <= \
        1e-6 * max(1.0, att["batch_pdp_j"]), \
        "per-request PDP attribution must sum to the batch total (§11.3)"
    got = sched.finished
    rids = sorted(rid2idx, key=rid2idx.get)
    steps = sum(got[r].steps for r in rids)
    lat = [done_at[k] - float(arrivals[k]) for k in sorted(done_at)]
    return {"tokens": [got[r].tokens for r in rids],
            "steps": steps, "wall_s": wall,
            "tok_s": steps / max(wall, 1e-9),
            **_latency_summary(lat),
            "kv_committed_bytes": sched.kv_committed_bytes,
            "kv_used_peak_bytes": sched.kv_used_peak,
            "kv_utilization": sched.kv_utilization_peak,
            "active_peak": sched.active_peak,
            "step_traces": sched.step_traces}


def _workload(cfg, smoke: bool, rng: np.random.Generator):
    """Shared-prefix trace: ``n_distinct`` hot utterances (think repeated
    audio preambles) drawn with reuse across ``n_req`` requests, Poisson
    arrival gaps at ~3x service rate so the queue backs up and peak
    concurrency probes the admission limit."""
    n_req, n_frames = (16, 16) if smoke else (24, 32)
    lo, hi = (4, 12) if smoke else (6, 16)
    n_distinct = 2 if smoke else 3
    distinct = [rng.standard_normal((1, n_frames, cfg.n_mels)
                                    ).astype(np.float32)
                for _ in range(n_distinct)]
    mels = [distinct[int(rng.integers(n_distinct))] for _ in range(n_req)]
    max_news = [int(rng.integers(lo, hi + 1)) for _ in range(n_req)]
    # step-unit Poisson gaps: mean service is mean(max_new) steps for
    # n_slots-at-once service; 3x load backs the queue up deterministically
    mean_gap = float(np.mean(max_news)) / (3 * 4)
    arrivals = np.floor(np.cumsum(rng.exponential(mean_gap, n_req)))
    return mels, max_news, arrivals, n_frames, hi


def _variant(name: str, cfg, params, quant: str, make_offload,
             smoke: bool, mesh=None, telemetry=None) -> Dict[str, object]:
    rng = np.random.default_rng(0)        # same trace for every variant
    mels, max_news, arrivals, n_frames, hi = _workload(cfg, smoke, rng)
    n_slots = 4
    max_len = hi + 8
    page_size = 4
    # paged geometry: 3x logical-slot oversubscription, self arena sized
    # to the MEAN budget (tail requests page-fault into preemption — the
    # admission-control point), cross arena sized to the distinct
    # utterance count + 1 (prefix sharing dedups the rest)
    n_slots_p = 3 * n_slots
    pages_per = -(-(int(np.mean(max_news)) + 1) // page_size)
    geom = dict(page_size=page_size, n_pages=1 + n_slots_p * pages_per,
                cross_page_size=n_frames,
                n_cross_pages=1 + len({id(m) for m in mels}))

    def engine(tele=None):
        return ServeEngine(cfg, params, max_len=max_len, quant=quant,
                           offload=make_offload(), eos_id=-1,
                           telemetry=tele)

    eng_c = engine()
    contig = _drive(eng_c.scheduler(n_slots=n_slots, n_frames=n_frames),
                    mels, max_news, arrivals)
    eng_p = engine(telemetry)
    sched_p = eng_p.paged_scheduler(n_slots=n_slots_p, n_frames=n_frames,
                                    **geom)
    paged = _drive(sched_p, mels, max_news, arrivals)

    # deliberately tight arena: fewer pages than the actives want, so
    # decode MUST preempt-and-recompute — and stay token-exact. Its own
    # telemetry proves the preempt/replay path keeps the §16.2 invariants
    tele_t = obs.Telemetry() if telemetry is not None else None
    eng_t = engine(tele_t)
    tight_pages = 2 + 2 * pages_per       # ~2 full slots' worth of pages
    sched_t = eng_t.paged_scheduler(n_slots=n_slots, n_frames=n_frames,
                                    page_size=page_size,
                                    n_pages=tight_pages,
                                    cross_page_size=n_frames,
                                    n_cross_pages=geom["n_cross_pages"])
    tight = _drive(sched_t, mels, max_news, arrivals)

    # admitted-requests-per-GB: peak concurrent admissions per committed
    # KV byte (the GB scaling cancels in the gated ratio)
    rpb_c = contig["active_peak"] / contig["kv_committed_bytes"]
    rpb_p = paged["active_peak"] / paged["kv_committed_bytes"]
    checks = {
        "parity": paged["tokens"] == contig["tokens"],
        "tight_parity": tight["tokens"] == contig["tokens"],
        "tight_preempted": sched_t.preemptions > 0,
        "shared_hits": sched_p.shared_hits > 0,
        "zero_retrace": (contig["step_traces"] == 1
                         and paged["step_traces"] == 1
                         and tight["step_traces"] == 1),
        "mem_2x": rpb_p >= 2 * rpb_c,
    }
    if telemetry is not None:
        # §16.2 invariants over the instrumented paged + tight engines:
        # exact ledger attribution, closed lifecycles, clean nesting —
        # through prefix hits, CoW splits, preemptions, and replays
        for tag, tl in (("paged", telemetry), ("tight", tele_t)):
            cons = tl.ledger_consistent()
            checks[f"tele_{tag}_ledger_exact"] = bool(cons["exact"])
            checks[f"tele_{tag}_spans_closed"] = tl.tracer.all_closed()
            checks[f"tele_{tag}_nesting"] = not tl.tracer.check_nesting()
    modes = {"contiguous": contig, "paged": paged, "tight": tight}
    if mesh is not None:
        # the multidev leg: the SAME paged geometry with the arenas'
        # page axes and the tables' slot axes sharded over "data"
        # (DESIGN.md §15.3) must stay token-exact and trace-stable
        eng_s = ServeEngine(cfg, params, max_len=max_len, quant=quant,
                            offload=make_offload(), eos_id=-1, mesh=mesh)
        sched_s = eng_s.paged_scheduler(n_slots=n_slots_p,
                                        n_frames=n_frames, **geom)
        sharded = _drive(sched_s, mels, max_news, arrivals)
        checks["sharded_parity"] = sharded["tokens"] == contig["tokens"]
        checks["sharded_zero_retrace"] = sharded["step_traces"] == 1
        modes["sharded"] = sharded
    return {"name": name, "n_slots": n_slots, "n_slots_paged": n_slots_p,
            "n_frames": n_frames, "geometry": geom,
            **{mode: {k: v for k, v in r.items() if k != "tokens"}
               for mode, r in modes.items()},
            "modes": list(modes),
            "preemptions": sched_t.preemptions,
            "shared_hits": sched_p.shared_hits,
            "req_per_gb_ratio": rpb_p / max(rpb_c, 1e-30),
            "checks": checks, "ok": all(checks.values())}


def run(smoke: bool = False, trace_out: str = None,
        metrics_out: str = None) -> dict:
    cfg = get_smoke_config("whisper-tiny") if smoke \
        else get_config("whisper-tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 448)
    mesh = None
    if len(jax.devices()) >= 2:           # the multidev CI leg
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh()
    tele = obs.Telemetry()                # rides the q8 paged engine
    variants = [
        _variant("dense", cfg, params, "none", lambda: None, smoke,
                 mesh=mesh),
        _variant("q8_0+offload", cfg, params, "q8_0",
                 lambda: OffloadEngine(prefer_pallas=False),
                 smoke, mesh=mesh, telemetry=tele),
    ]

    rows = []
    for v in variants:
        for mode in v["modes"]:
            r = v[mode]
            rows.append([v["name"], mode, f"{r['tok_s']:.1f}",
                         f"{r['p95_steps']:.0f}", f"{r['p99_steps']:.0f}",
                         f"{r['kv_committed_bytes'] / 1024:.0f}",
                         f"{r['kv_utilization']:.2f}",
                         str(r["active_peak"])])
    print("whisper-tiny paged vs contiguous KV serving, shared-prefix "
          f"Poisson trace ({'smoke' if smoke else 'full'} config)")
    print(fmt_table(rows, ["variant", "mode", "tok/s", "p95(steps)",
                           "p99(steps)", "KV committed(KiB)", "KV util",
                           "peak active"]))
    ok = True
    for v in variants:
        ok = ok and v["ok"]
        detail = " ".join(f"{k}={'ok' if val else 'FAIL'}"
                          for k, val in v["checks"].items())
        print(f"{v['name']}: {v['req_per_gb_ratio']:.2f}x requests/GB, "
              f"{v['shared_hits']} prefix hits, {v['preemptions']} "
              f"preemptions (tight) | {detail} "
              f"-> {'ok' if v['ok'] else 'FAIL'}")
    if trace_out:
        print("trace written:", tele.write_trace(trace_out))
    if metrics_out:
        print("metrics written:", tele.write_metrics(metrics_out))
    out = {"smoke": smoke, "variants": variants, "gate_ok": ok,
           "ledger_consistency": tele.ledger_consistent()}
    save("paged_serving", out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for the CI gate")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the q8 paged engine's Perfetto trace")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write its Prometheus metrics exposition")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke, trace_out=args.trace_out,
              metrics_out=args.metrics_out)
    return 0 if out["gate_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
