#!/usr/bin/env python3
"""Calibration sweeps on the chip, in one process: the slot count of a
closed-loop cell, then the arrival rate of an open-loop cell.

    python3 chip_bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --slots 16,32,64,128            # closed loop: tokens/s per slot count
    python3 chip_bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 10,20,30 [--slots 32]   # open loop: latency per rate

Each point is one full run of the cell with that value put in its place
(the check against the reference included). For an open loop it prints
the queue wait of the window's first and second half and how long the
requests due in the window took to drain after it: a growing backlog
shows as a second half that waits longer and a drain that grows with the
window. Prints one JSON line per point.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chip_bench import harness, spec, stats  # noqa: E402


def point(cell, seed, seconds):
    t = time.perf_counter()
    out = harness.run_cell(cell, seed, seconds, False, t,
                           report_programs=True)
    run, res = out["run"], out["result"]
    line = {"workload": cell["name"], "n_slots": cell["config"]["deployment"]["n_slots"],
            "correct": res["correct"], "failed": res["failed"],
            "attempted": res["attempted"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "gap": res["checks"]["logit_gap"]["value"],
            "programs": [x for x in out["lines"] if x.startswith("program ")]}
    steps = [b - a for a, b, _ in run.steps if run.in_window(b)]
    if steps:
        line["step_ms_mean"] = 1e3 * stats.mean(steps)
    due = [r for r in run.requests.values() if r.due is not None
           and run.in_window(r.due)]
    if due:
        line["rate_per_s"] = cell["traffic"]["rate_per_s"]
        mid = (run.t0 + run.t1) / 2
        for half, sel in (("first", lambda r: r.due < mid),
                          ("second", lambda r: r.due >= mid)):
            w = [r.prefill_start - r.due for r in due
                 if sel(r) and r.prefill_start is not None]
            if w:
                line[f"queue_wait_p50_ms_{half}"] = 1e3 * stats.percentile(
                    w, 50)
        ends = [r.token_t[-1] for r in due if r.token_t]
        line["drain_s"] = max(ends) - run.t1 if ends else None
    print(json.dumps(line), flush=True)
    print("\n".join(out["lines"]), file=sys.stderr, flush=True)
    del out, run
    gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--slots", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    base = spec.resolve(spec.load_spec(), args.workload)
    why = harness.require_chips(base["chips"])
    if why is not None:
        print(f"sweep: {why}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    slots = [int(s) for s in args.slots.split(",") if s]
    rates = [float(r) for r in args.rates.split(",") if r]
    for n in slots if not rates else slots[:1] or [None]:
        for r in rates or [None]:
            cell = copy.deepcopy(base)
            if n is not None:
                cell["config"]["deployment"]["n_slots"] = n
            if r is not None:
                cell["traffic"]["rate_per_s"] = r
            try:
                point(cell, args.seed, args.seconds)
            except Exception as e:      # an out-of-memory point is a result
                print(json.dumps({"n_slots": n, "rate_per_s": r,
                                  "error": repr(e)[:400]}), flush=True)
                gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
