"""Speculative decoding unified with continuous-batching and paged-KV
serving (DESIGN.md §17.4; the paper's §5.1 sustained multi-utterance
evaluation run through the §17 two-model ladder).

Queued utterances admit into freed wave rows at round boundaries, the
(B, k+1) verify window reads/writes the §15 page arena through block
tables (multi-entry scatter, windows straddling page boundaries), and
the pre-round capacity pass preempts-and-replays when a tight arena
runs dry. The gates, asserted every run (CI via ``--smoke`` on the
default AND multidev legs):

  - token-exact parity: under a deterministic Poisson arrival trace
    with mid-flight admission, the round-boundary schedulers
    (``SpecContinuousScheduler`` AND ``PagedSpecScheduler``) reproduce
    BOTH references exactly — the run-to-completion ``SpecScheduler``
    wave and plain greedy on the verifier alone — for dense f32 and
    q8_0+offload
  - tight-arena parity: a page arena too small for the active set
    forces preempt-and-replay mid-schedule (``preemptions > 0``
    asserted) and still reproduces both references token-exactly
  - mid-flight admission: requests really are admitted while earlier
    requests hold live rows (``midflight > 0`` asserted), so the
    round-boundary path is exercised, not just batch-start admission
  - zero step retraces: across each whole drain the verify window and
    the draft step compile exactly once per engine
  - exact attribution: per-request PDP sums to the batch total every
    drive (asserted in ``_drive``); on q8_0+offload the shared ledger's
    by_role split sums to the flop totals and the §16.2 ledger spans
    claim every committed FLOP

Workload: the reduced ladder + echo parameterization from
``benchmarks.speculative`` (tiny draft, base-rung verifier, decoder
blocks scaled toward identity so acceptance is high); arrival gaps are
Poisson in round units on a virtual clock, so the trace is
machine-independent. ``--trace-out``/``--metrics-out`` export the q8
paged engine's Perfetto trace (validated by tools/check_trace.py in CI)
and metrics exposition.

Usage:
  PYTHONPATH=src python -m benchmarks.paged_speculative [--smoke]
      [--trace-out PATH] [--metrics-out PATH]

Writes experiments/bench/paged_speculative.json.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import fmt_table, save
from benchmarks.speculative import _echo_params, _ladder_cfg
from repro import obs
from repro.core.offload import OffloadEngine
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine
from repro.serve.speculative import SpecScheduler

K = 4


def _workload(cfg, smoke: bool, rng: np.random.Generator):
    """Distinct utterances with varied budgets; Poisson arrival gaps in
    round units at ~2x service rate so admissions land mid-flight."""
    n_req, n_frames = (8, 16) if smoke else (14, 32)
    lo, hi = (4, 10) if smoke else (6, 16)
    mels = [rng.standard_normal((1, n_frames, cfg.n_mels)).astype(np.float32)
            for _ in range(n_req)]
    max_news = [int(rng.integers(lo, hi + 1)) for _ in range(n_req)]
    # a round emits ~k+1 tokens/row at echo acceptance: mean service is
    # max_new/(k+1) rounds; 2x load on 2 slots backs the queue up
    mean_gap = float(np.mean(max_news)) / (K + 1) / (2 * 2)
    arrivals = np.floor(np.cumsum(rng.exponential(mean_gap, n_req)))
    return mels, max_news, arrivals, n_frames, hi


def _drive(sched, mels: List[np.ndarray], max_news: List[int],
           arrivals: np.ndarray) -> Dict[str, object]:
    """Replay the arrival trace on a virtual round clock (one unit per
    speculative round), counting admissions that land while earlier
    requests hold live rows — the §17.4 round-boundary path."""
    t, i, n = 0, 0, len(mels)
    rid2idx: Dict[int, int] = {}
    midflight = 0
    wall0 = time.perf_counter()
    while i < n or sched.n_queued or sched.n_active:
        while i < n and arrivals[i] <= t:
            rid2idx[sched.submit(mels[i], max_new=max_news[i])] = i
            i += 1
        was_active = sched.n_active
        admitted = sched.admit()
        if was_active and admitted:
            midflight += len(admitted)
        if sched.n_active:
            sched.decode_step()
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
    return {"tokens": [got[r].tokens for r in rids],
            "steps": steps, "wall_s": wall,
            "tok_s": steps / max(wall, 1e-9),
            "midflight": midflight,
            "rounds": t}


def _variant(name: str, quant: str, make_offload, smoke: bool,
             telemetry=None) -> Dict[str, object]:
    rng = np.random.default_rng(0)        # same trace for every variant
    vcfg = _ladder_cfg("base")
    dcfg = _ladder_cfg("tiny")
    alpha = 0.02
    vparams = _echo_params(model_lib.init_params(jax.random.PRNGKey(1),
                                                 vcfg), alpha)
    dparams = _echo_params(model_lib.init_params(jax.random.PRNGKey(0),
                                                 dcfg), alpha)
    mels, max_news, arrivals, n_frames, hi = _workload(vcfg, smoke, rng)
    n_slots = 2
    max_len = hi + K + 2                  # submit guard: max_new + k + 1

    def spec_of(eng):
        return eng.speculative(dcfg, dparams, k=K)

    def engine(tele=None):
        return ServeEngine(vcfg, vparams, max_len=max_len, quant=quant,
                           offload=make_offload(), eos_id=-1,
                           telemetry=tele)

    # reference 1: plain greedy on the verifier alone, batch-1
    eng_g = engine()
    greedy = [eng_g.transcribe(m, sot_id=1, max_new=mn)[0].tokens
              for m, mn in zip(mels, max_news)]
    # reference 2: the run-to-completion SpecScheduler wave (§17.4)
    eng_w = engine()
    wave_sch = SpecScheduler(spec_of(eng_w), n_slots=n_slots)
    rids = [wave_sch.submit(m, max_new=mn)
            for m, mn in zip(mels, max_news)]
    wres = wave_sch.run()
    wave = [wres[r].tokens for r in rids]

    # round-boundary admission on the contiguous slot pool
    eng_c = engine()
    spec_c = spec_of(eng_c)
    contig = _drive(spec_c.continuous(n_slots=n_slots, n_frames=n_frames),
                    mels, max_news, arrivals)

    # the paged arena, roomy: every slot can hold its full budget
    pages_per = -(-max_len // 4)
    geom = dict(page_size=4, n_pages=1 + n_slots * pages_per,
                cross_page_size=n_frames, n_cross_pages=1 + n_slots)
    eng_p = engine(telemetry)
    spec_p = spec_of(eng_p)
    paged = _drive(spec_p.paged(n_slots=n_slots, n_frames=n_frames, **geom),
                   mels, max_news, arrivals)

    # deliberately tight arena: ONE slot's worth of self pages (any
    # single request still fits), so two live rows MUST collide in the
    # pre-round capacity pass and preempt-and-replay mid-schedule
    tele_t = obs.Telemetry() if telemetry is not None else None
    eng_t = engine(tele_t)
    spec_t = spec_of(eng_t)
    sched_t = spec_t.paged(n_slots=n_slots, n_frames=n_frames,
                           page_size=4, n_pages=1 + pages_per,
                           cross_page_size=n_frames,
                           n_cross_pages=1 + n_slots)
    tight = _drive(sched_t, mels, max_news, arrivals)

    checks = {
        "wave_is_greedy": wave == greedy,
        "contig_parity": contig["tokens"] == greedy,
        "paged_parity": paged["tokens"] == greedy,
        "tight_parity": tight["tokens"] == greedy,
        "midflight_admission": (contig["midflight"] > 0
                                and paged["midflight"] > 0),
        "tight_preempted": sched_t.preemptions > 0,
        "zero_retrace": all(
            s.verifier._verify_traces == 1 and s.draft._step_traces == 1
            for s in (spec_c, spec_p, spec_t)),
    }
    report: Dict[str, object] = {}
    if quant == "q8_0":
        s = eng_p.offload.stats
        total = s.offloaded_flops + s.fallback_flops + s.residual_flops
        checks["by_role_sums"] = sum(s.by_role.values()) == total
        report["by_role"] = dict(s.by_role)
    if telemetry is not None:
        for tag, tl in (("paged", telemetry), ("tight", tele_t)):
            cons = tl.ledger_consistent()
            checks[f"tele_{tag}_ledger_exact"] = bool(cons["exact"])
            checks[f"tele_{tag}_spans_closed"] = tl.tracer.all_closed()
            checks[f"tele_{tag}_nesting"] = not tl.tracer.check_nesting()
    acc = spec_p.acceptance_rate()
    modes = {"contiguous": contig, "paged": paged, "tight": tight}
    return {"name": name, "k": K, "n_slots": n_slots, "geometry": geom,
            **{mode: {k: v for k, v in r.items() if k != "tokens"}
               for mode, r in modes.items()},
            "modes": list(modes),
            "acceptance": acc,
            "preemptions": sched_t.preemptions,
            "checks": checks, "ok": all(checks.values())}


def run(smoke: bool = False, trace_out: str = None,
        metrics_out: str = None) -> dict:
    tele = obs.Telemetry()                # rides the q8 paged engine
    variants = [
        _variant("dense", "none", lambda: None, smoke),
        _variant("q8_0+offload", "q8_0",
                 lambda: OffloadEngine(prefer_pallas=False),
                 smoke, telemetry=tele),
    ]

    rows = []
    for v in variants:
        for mode in v["modes"]:
            r = v[mode]
            rows.append([v["name"], mode, f"{r['tok_s']:.1f}",
                         str(r["rounds"]), str(r["midflight"]),
                         f"{v['acceptance']:.2f}"])
    print(f"paged + continuous speculative serving, reduced ladder, "
          f"k={K} ({'smoke' if smoke else 'full'})")
    print(fmt_table(rows, ["variant", "mode", "tok/s", "rounds",
                           "midflight admits", "accept"]))
    ok = True
    for v in variants:
        ok = ok and v["ok"]
        detail = " ".join(f"{k}={'ok' if val else 'FAIL'}"
                          for k, val in v["checks"].items())
        print(f"{v['name']}: {v['preemptions']} preemptions (tight) | "
              f"{detail} -> {'ok' if v['ok'] else 'FAIL'}")
    if trace_out:
        print("trace written:", tele.write_trace(trace_out))
    if metrics_out:
        print("metrics written:", tele.write_metrics(metrics_out))
    out = {"smoke": smoke, "variants": variants, "gate_ok": ok,
           "ledger_consistency": tele.ledger_consistent()}
    save("paged_speculative", out)
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
