#!/usr/bin/env python3
"""The readings a cell's logit-gap limit is set from, on the chip, in
one process: for each seed, a short window at the cell's own load and
sizes, then the widest gap of the served tokens below the float32
reference's best (the program's reading) and, at the same positions of
the same requests, the widest gap of the tokens the reference with its
weights rounded to int4 in blocks of 32 puts first (the control's). The
control's first choices, put in place of the served tokens, go through
the run's own ``correct`` (``harness.judge``): ``control_correct`` has to
read false, and the program's ``correct`` true.

    python3 chip_bench/control.py --workload <cell> --seeds 1,2,3 --seconds 4

The benchmark's own runs never run this. One JSON line per seed on
standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chip_bench import harness, spec  # noqa: E402

CONTROL_BITS = 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    cell = spec.resolve(spec.load_spec(), args.workload)
    why = harness.require_chips(cell["chips"])
    if why is not None:
        print(f"control: {why}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), control_bits=CONTROL_BITS)
        served = [float(g.max()) for g in out["served_gaps"]]
        control = [float(g.max()) for g in out["control_gaps"]]
        line = {"workload": args.workload, "seed": seed,
                "requests": len(served),
                "tokens": sum(len(g) for g in out["served_gaps"]),
                "program_gap": max(served), "control_gap": max(control),
                "control_positions_flipped": sum(
                    int((g > 0).sum()) for g in out["control_gaps"]),
                "correct": out["result"]["correct"],
                "control_correct": out["control"]["correct"]}
        print(json.dumps(line), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
