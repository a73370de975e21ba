"""Continuous vs static batching under Poisson arrivals (DESIGN.md §11;
the serving analog of the paper's §5.1 sustained multi-utterance E2E
evaluation).

Static run-to-completion batches lose utilization two ways the paper's
always-busy accelerator forbids: early-finished rows burn jitted steps
until the batch drains, and new arrivals head-of-line block behind it.
This benchmark replays the SAME staggered Poisson arrival trace through
both serving modes on whisper-tiny (dense bf16 and Q8_0+offload) and
reports aggregate tok/s, p50/p95 request latency, and PDP.

Method: a virtual-clock discrete-event replay driven by *calibrated*
service times — batch prefill, batch decode step, scheduler admission
(batch-1 prefill + slot splice + bookkeeping) and scheduler step (incl.
its host sync) are each estimated as the MINIMUM over interleaved
repeated probes (timing noise on a shared machine is strictly additive,
so the min is the robust estimate of an op's true cost), then the
arrival trace is replayed through both modes advancing the clock by
those constants. Every prefill/step still executes for real (token
streams, ledger commits, retrace counting are all live); only the clock
uses the calibrated constants, so a single noisy call on a shared CI
machine cannot flip the comparison. No sleeping — the run is fast and
deterministic given the probes.

Invariants asserted every run (exit code gates CI via ``--smoke``):
  - continuous >= static on aggregate tok/s AND <= on p95 latency
  - zero decode step_fn retraces after warmup (fixed-shape slot pool)
  - per-request ledger PDP attribution sums to the batch total
  - telemetry (DESIGN.md §16) invariants on a dedicated q8_0+offload
    drain: every lifecycle span closes, span nesting holds, and the sum
    of ledger-span FLOP deltas equals the ledger total EXACTLY (§16.2).
    The drain is OUTSIDE the gated measurement — span recording is host
    work per step, and the vs-static gate calibrates per-step cost, so
    attaching telemetry there would fold its overhead into the gated
    constants (the overhead budget itself is gated by
    ``benchmarks.telemetry_overhead``)

Latency percentiles (p50/p95/p99) come from the shared ``obs.metrics``
histogram in exact (track_values) mode — one percentile implementation
across the serving benchmarks, with the CI gates still comparing exact
values, never bucket edges.

Usage:
  PYTHONPATH=src python -m benchmarks.continuous_batching [--smoke]
      [--trace-out PATH] [--metrics-out PATH]

Writes experiments/bench/continuous_batching.json.
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
from repro.core import energy
from repro.core.offload import OffloadEngine
from repro.models import model as model_lib
from repro.obs.metrics import LATENCY_BUCKETS_S, Histogram
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler


def _latency_summary(xs: List[float]) -> Dict[str, float]:
    """p50/p95/p99 through the ONE shared percentile implementation
    (repro.obs.metrics, DESIGN.md §16.3), in exact mode: the continuous-
    vs-static p95 gate compares real values, so the summary must not
    quantize to bucket edges."""
    h = Histogram("latency_s", LATENCY_BUCKETS_S, track_values=True)
    for x in xs:
        h.observe(x)
    return {"p50_s": h.percentile(50), "p95_s": h.percentile(95),
            "p99_s": h.percentile(99)}


def _calibrate(engine: ServeEngine, mel0: np.ndarray, n_slots: int,
               n_frames: int, rounds: int = 5) -> Dict[str, float]:
    """Min-over-probes service times for the virtual clock. Warmup
    (compilation of the batch-B static path, the batch-1 admission
    prefill, and the shared decode step) happens first; then the
    static-path and scheduler-path probes run INTERLEAVED round-robin so
    a noisy patch on a shared machine lands on both modes' samples alike
    — the gated comparison depends on the ratio of the two modes'
    per-step costs (the same compiled step plus each mode's own host
    overhead), and min-over-interleaved-rounds keeps that ratio stable."""
    warm = np.concatenate([mel0] * n_slots, axis=0)
    engine.transcribe(warm, max_new=6)                       # compile
    sched = ContinuousBatchingScheduler(engine, n_slots=n_slots,
                                        n_frames=n_frames)
    sched.submit(mel0, max_new=2)
    sched.run()                                              # compile admit
    pf_b, st_b, admits, csteps = [], [], [], []
    for _ in range(rounds):
        r = engine.transcribe(warm, max_new=6)
        pf_b.append(r[0].prefill_s * n_slots)
        st_b.append(r[0].decode_s * n_slots / max(r[0].steps, 1))
        for _ in range(2):
            sched.submit(mel0, max_new=4)
        while sched.n_queued or sched.n_active:
            if sched.n_queued and sched.pool.n_free:
                t0 = time.perf_counter()
                n = len(sched.admit())
                admits.append((time.perf_counter() - t0) / max(n, 1))
            t0 = time.perf_counter()
            sched.decode_step()
            csteps.append(time.perf_counter() - t0)
    # min, not median: timing noise on a shared machine is strictly
    # additive, so the minimum is the robust estimate of each op's true
    # cost — and since the replay is deterministic given these constants,
    # it is the only run-to-run variance source for the gated comparison
    return {"t_prefill_b": float(np.min(pf_b)),
            "t_step_b": float(np.min(st_b)),
            "t_admit": float(np.min(admits)),
            "t_cstep": float(np.min(csteps))}


def _run_static(engine: ServeEngine, mels: List[np.ndarray],
                max_news: List[int], arrivals: np.ndarray, n_slots: int,
                cal: Dict[str, float]) -> Dict[str, float]:
    """Static run-to-completion batching on the arrival trace: when the
    engine frees up it takes the up-to-``n_slots`` oldest *arrived*
    requests (padding the batch to the fixed width by repeating the last
    utterance — shapes stay static) and decodes the whole batch to the
    max of its members' budgets; members all complete at batch drain."""
    t, done_t, tokens = 0.0, {}, 0
    i, n = 0, len(mels)
    while i < n:
        t = max(t, float(arrivals[i]))                # wait for work
        j = i + 1                                     # take what has arrived
        while j - i < n_slots and j < n and arrivals[j] <= t:
            j += 1
        members = list(range(i, j))
        batch = [mels[k] for k in members]
        while len(batch) < n_slots:                   # fixed-shape pad
            batch.append(batch[-1])
        mel = np.concatenate(batch, axis=0)
        budget = max(max_news[k] for k in members)
        res = engine.transcribe(mel, max_new=budget)  # real execution
        t += cal["t_prefill_b"] + res[0].steps * cal["t_step_b"]
        for k in members:
            done_t[k] = t
            tokens += min(max_news[k], res[0].steps)  # row's useful tokens
        i = j
    lat = [done_t[k] - float(arrivals[k]) for k in range(n)]
    return {"tok_s": tokens / max(t, 1e-9), **_latency_summary(lat),
            "makespan_s": t,
            "tokens": tokens, "pdp_j": energy.pdp(t, energy.TPU_V5E_W)}


def _run_continuous(engine: ServeEngine, mels: List[np.ndarray],
                    max_news: List[int], arrivals: np.ndarray,
                    n_slots: int, n_frames: int,
                    cal: Dict[str, float]) -> Dict[str, float]:
    """Continuous batching on the same trace: arrivals are released to the
    scheduler at their Poisson timestamps; admissions and steps advance
    the clock by their calibrated costs; requests complete at their own
    eviction step."""
    sched = ContinuousBatchingScheduler(engine, n_slots=n_slots,
                                        n_frames=n_frames)
    t, done_t = 0.0, {}
    rid2idx: Dict[int, int] = {}
    pending = list(range(len(mels)))
    while pending or sched.n_queued or sched.n_active:
        while pending and arrivals[pending[0]] <= t:
            idx = pending.pop(0)
            rid2idx[sched.submit(mels[idx], max_new=max_news[idx])] = idx
        if sched.n_queued and sched.pool.n_free:
            t += len(sched.admit()) * cal["t_admit"]  # real execution
        if sched.n_active:
            events = sched.decode_step()              # real execution
            t += cal["t_cstep"]
            for ev in events:
                if ev.done:
                    done_t[rid2idx[ev.rid]] = t
        elif pending:
            t = max(t, float(arrivals[pending[0]]))   # idle: jump to arrival
    n = len(mels)
    lat = [done_t[k] - float(arrivals[k]) for k in range(n)]
    tokens = sum(r.steps for r in sched.finished.values())
    att = sched.attribution()
    per_req_sum = sum(att["per_request_pdp_j"].values())
    assert abs(per_req_sum - att["batch_pdp_j"]) <= \
        1e-6 * max(1.0, att["batch_pdp_j"]), \
        "per-request PDP attribution must sum to the batch total (§11.3)"
    return {"tok_s": tokens / max(t, 1e-9), **_latency_summary(lat),
            "makespan_s": t,
            "tokens": tokens, "pdp_j": energy.pdp(t, energy.TPU_V5E_W),
            "attributed_pdp_j": per_req_sum,
            # KV memory accounting (DESIGN.md §15.4): bytes the pool
            # commits up front, and peak fraction holding live data
            "kv_committed_bytes": sched.kv_committed_bytes,
            "kv_utilization": sched.kv_utilization_peak}


def _variant(name: str, cfg, params, quant: str, offload, smoke: bool,
             rng: np.random.Generator) -> Dict[str, object]:
    n_slots = 4
    n_req, n_frames = (12, 16) if smoke else (16, 64)
    # wide max_new spread: the decode budgets' variance is where static
    # batching wastes steps (drained rows idle until the batch max)
    lo, hi = (4, 32) if smoke else (6, 48)
    engine = ServeEngine(cfg, params, max_len=hi + 8, quant=quant,
                         offload=offload, eos_id=-1)
    mels = [rng.standard_normal((1, n_frames, cfg.n_mels)).astype(np.float32)
            for _ in range(n_req)]
    max_news = [int(rng.integers(lo, hi + 1)) for _ in range(n_req)]

    cal = _calibrate(engine, mels[0], n_slots, n_frames)
    traces0 = engine._step_traces

    # Poisson arrivals at ~3x load: mean service per request is
    # mean(max_new) steps of a batch that serves n_slots at once
    mean_gap = cal["t_step_b"] * float(np.mean(max_news)) / (3 * n_slots)
    arrivals = np.cumsum(rng.exponential(mean_gap, n_req))

    st = _run_static(engine, mels, max_news, arrivals, n_slots, cal)
    co = _run_continuous(engine, mels, max_news, arrivals, n_slots,
                         n_frames, cal)
    retraces = engine._step_traces - traces0
    return {"name": name, "static": st, "continuous": co, "cal": cal,
            "retraces_after_warmup": retraces,
            "speedup_tok_s": co["tok_s"] / max(st["tok_s"], 1e-9),
            "p95_ratio": st["p95_s"] / max(co["p95_s"], 1e-9),
            "n_req": n_req, "n_slots": n_slots, "n_frames": n_frames,
            "mean_gap_s": float(mean_gap)}


def _telemetry_drain(cfg, params, smoke: bool) -> obs.Telemetry:
    """Dedicated q8_0+offload scheduler drain carrying telemetry, for the
    §16.2 invariant checks. Deliberately NOT the gated engines: the
    vs-static gate replays calibrated per-step costs, and span recording
    is real host work per step — its budget is gated separately by
    ``benchmarks.telemetry_overhead``."""
    rng = np.random.default_rng(7)
    tele = obs.Telemetry()
    engine = ServeEngine(cfg, params, max_len=24, quant="q8_0",
                         offload=OffloadEngine(prefer_pallas=False),
                         eos_id=-1, telemetry=tele)
    sched = ContinuousBatchingScheduler(engine, n_slots=2, n_frames=16)
    for _ in range(4 if smoke else 6):
        mel = rng.standard_normal((1, 16, cfg.n_mels)).astype(np.float32)
        sched.submit(mel, max_new=int(rng.integers(3, 8)))
    sched.run()
    return tele


def run(smoke: bool = False, trace_out: str = None,
        metrics_out: str = None) -> dict:
    cfg = get_smoke_config("whisper-tiny") if smoke \
        else get_config("whisper-tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 448)
    variants = []
    for name, quant, off in [
            ("dense", "none", None),
            ("q8_0+offload", "q8_0",
             OffloadEngine(prefer_pallas=False))]:
        rng = np.random.default_rng(0)          # same trace both variants
        variants.append(_variant(name, cfg, params, quant, off, smoke, rng))
    tele = _telemetry_drain(cfg, params, smoke)

    rows = []
    for v in variants:
        for mode in ("static", "continuous"):
            r = v[mode]
            rows.append([v["name"], mode, f"{r['tok_s']:.1f}",
                         f"{r['p50_s']*1e3:.1f}", f"{r['p95_s']*1e3:.1f}",
                         f"{r['p99_s']*1e3:.1f}",
                         f"{r['pdp_j']:.1f}",
                         (f"{r['kv_committed_bytes']/1024:.0f}"
                          if "kv_committed_bytes" in r else "-"),
                         (f"{r['kv_utilization']:.2f}"
                          if "kv_utilization" in r else "-")])
    print("whisper-tiny serving under staggered Poisson arrivals "
          f"({'smoke' if smoke else 'full'} config)")
    print(fmt_table(rows, ["variant", "mode", "tok/s", "p50(ms)", "p95(ms)",
                           "p99(ms)", "PDP(J)", "KV committed(KiB)",
                           "KV util"]))
    ok = True
    for v in variants:
        win = (v["speedup_tok_s"] >= 1.0
               and v["continuous"]["p95_s"] <= v["static"]["p95_s"])
        zero_retrace = v["retraces_after_warmup"] == 0
        ok = ok and win and zero_retrace
        print(f"{v['name']}: continuous {v['speedup_tok_s']:.2f}x tok/s, "
              f"p95 {v['p95_ratio']:.2f}x lower, "
              f"{v['retraces_after_warmup']} retraces after warmup "
              f"-> {'ok' if win and zero_retrace else 'FAIL'}")
    cons = tele.ledger_consistent()
    tele_checks = {"ledger_exact": bool(cons["exact"]),
                   "spans_closed": tele.tracer.all_closed(),
                   "nesting_ok": not tele.tracer.check_nesting()}
    ok = ok and all(tele_checks.values())
    print("telemetry: " + " ".join(f"{k}={'ok' if val else 'FAIL'}"
                                   for k, val in tele_checks.items())
          + f" (claimed {cons['claimed_flops']} == "
            f"ledger {cons['ledger_flops']} FLOPs)")
    if trace_out:
        print("trace written:", tele.write_trace(trace_out))
    if metrics_out:
        print("metrics written:", tele.write_metrics(metrics_out))
    out = {"smoke": smoke, "variants": variants, "gate_ok": ok,
           "telemetry_checks": tele_checks, "ledger_consistency": cons}
    save("continuous_batching", out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for the CI gate")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the q8_0+offload variant's Perfetto trace")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write its Prometheus metrics exposition")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke, trace_out=args.trace_out,
              metrics_out=args.metrics_out)
    return 0 if out["gate_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
