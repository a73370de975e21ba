#!/usr/bin/env python3
"""Profile one benchmark cell and keep what the benchmark's traced run
throws away: the program's spans beside the device's idle time, and what
the host was doing inside every long call.

    python3 chip_bench/profile_cell.py --workload <cell> --seed <n> \\
        --window <s> --stretch <s> --out <dir> [--keep-trace]

Set-up and warm-up as ``harness.run_cell``; then ``--window`` seconds of
the cell's load with no profiler (to reach steady state, and to time the
calls untraced), then ``--stretch`` seconds of it under the profiler
inside ``bench.stretch``. Writes ``<dir>/<cell>.<seed>.json``: the
``trace_reduce`` reduction, the ``program_spans`` reduction, its idle
split and the device seconds per model scope, the mean host time of a decode step and an admission pass inside
the window and inside the stretch, and for each call over
``--long-ms`` in the stretch the host events under it, summed by name,
and what the device ran meanwhile.
Runs on a checkout whose program has no ``repro.`` spans too: the
program keys are then empty. No reference check: this is no benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import lzma
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from chip_bench import (harness, program_spans, spec, trace_reduce,  # noqa: E402,E501
                        traffic)


def _mean_ms(calls, lo, hi):
    d = [b - a for a, b, _ in calls if lo <= b <= hi]
    return 1e3 * sum(d) / len(d) if d else None


def _under_long_calls(pd, raw: dict, long_ns: float, lo: float,
                      hi: float, top: int = 12):
    """For each harness call (``bench.`` span, sleeps aside) over
    ``long_ns`` inside [lo, hi] (ns): the program spans inside it, the
    host events inside it on every line summed by name, and what the first
    device ran meanwhile (busy ms, the programs and the longest ops)."""
    events = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                events += [(e.start_ns, e.start_ns + e.duration_ns,
                            e.name[:96], ln.name) for e in ln.events]
    dev = raw["devices"][min(raw["devices"])] if raw["devices"] else None
    out = []
    for a, b, name in raw["host"]:
        if b - a < long_ns or name in (trace_reduce.STRETCH, "bench.idle") \
                or not (lo <= a and b <= hi):
            continue
        sums = defaultdict(lambda: [0, 0.0, 0.0])
        for s, t, n, line in events:
            if s >= a and t <= b and (s, t) != (a, b):
                v = sums[f"{line}: {n}"]
                v[0] += 1
                v[1] += (t - s) * 1e-6
                v[2] = max(v[2], (t - s) * 1e-6)
        call = {
            "span": name, "ms": (b - a) * 1e-6,
            "program_spans": sorted(
                ({"span": n, "ms": (t - s) * 1e-6, "rid": r}
                 for s, t, n, r in raw["program"] if s >= a and t <= b),
                key=lambda x: -x["ms"])[:top],
            "host_events": [{"event": k, "count": c, "ms": ms,
                             "longest_ms": mx} for k, (c, ms, mx) in
                            sorted(sums.items(),
                                   key=lambda kv: -kv[1][2])[:top]]}
        if dev is not None:
            ops = [(max(s, a), min(t, b), n) for s, t, n in dev["ops"]
                   if t > a and s < b]
            busy = trace_reduce._union([(s, t) for s, t, _ in ops])
            call["device_busy_ms"] = sum(t - s for s, t in busy) * 1e-6
            call["device_programs"] = [
                {"program": n.split("(")[0], "ms": (t - s) * 1e-6}
                for s, t, n in dev["modules"] if t > a and s < b][:top]
            call["device_longest_ops"] = [
                {"op": trace_reduce._op_name(n), "ms": (t - s) * 1e-6}
                for s, t, n in sorted(ops, key=lambda o: o[0] - o[1])[:5]]
        out.append(call)
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--window", type=float, default=10.0)
    ap.add_argument("--stretch", type=float, required=True)
    ap.add_argument("--long-ms", type=float, default=300.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_spec(), args.workload)
    harness.enable_compile_cache()
    conf = cell["config"]
    n_slots = conf["deployment"]["n_slots"]
    cfg, engine = harness.build(cell, args.seed)
    sched = engine.scheduler(n_slots=n_slots, n_frames=cfg.encoder_ctx)
    tr = traffic.Traffic(cell["traffic"], args.seed, args.window, n_slots,
                         cfg.n_mels)
    warm = harness.Client(sched, traced=False)
    warm.submit(tr.warm_request(), None)
    while sched.n_queued or sched.n_active:
        warm.cycle()
    sched.finished.clear()
    total = args.window + args.stretch
    if tr.kind == "backlog":
        tr.prepare(traffic.expected_backlog_requests(
            cell["traffic"]["expected_tokens_per_s"], total,
            cell["traffic"]["out_tokens"]) + tr.depth)
    else:
        tr.prepare()
        more = traffic.Traffic(cell["traffic"], args.seed + 2**40,
                               args.stretch, n_slots, cfg.n_mels)
        more.prepare()
    jax.block_until_ready(sched.pool.state)
    setup_s = time.perf_counter() - t_start

    d = harness.Client(sched, traced=True)
    with harness.GcPauses() as gcs:
        if tr.kind == "backlog":
            t0, t1, nxt = harness.closed_loop(d, tr, args.window)
            cont = lambda: harness.closed_loop(d, tr, args.stretch, nxt)  # noqa: E731,E501
        else:
            t0, t1, _ = harness.open_loop(d, tr, args.window, drain=False)
            cont = lambda: harness.open_loop(d, more, args.stretch,  # noqa: E731,E501
                                             drain=False)
        log_dir = tempfile.mkdtemp(prefix="chip_bench_profile_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.STRETCH):
                a = time.perf_counter()
                cont()
                jax.block_until_ready(d.sched.pool.state)
                b = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
    path = harness._xplane(log_dir)
    pd = ProfileData.from_file(path)
    raw = program_spans.from_profile(pd)
    red = trace_reduce.reduce(raw)
    prog = program_spans.reduce(raw)
    per = ({"repro.admit": "repro.prefill"} if tr.kind == "poisson"
           else {"repro.admit": "repro.decode_step"})
    lo_ns = min(h[0] for h in raw["host"] if h[2] == trace_reduce.STRETCH)
    hi_ns = max(h[1] for h in raw["host"] if h[2] == trace_reduce.STRETCH)
    result = {
        "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
        "device": jax.devices()[0].device_kind,
        "window": {"seconds": t1 - t0,
                   "step_ms": _mean_ms(d.steps, t0, t1),
                   "admit_ms": _mean_ms(d.admits, t0, t1),
                   "steps": sum(1 for _, e, _ in d.steps if t0 <= e <= t1)},
        "stretch": {"seconds": b - a,
                    "step_ms": _mean_ms(d.steps, a, b),
                    "admit_ms": _mean_ms(d.admits, a, b),
                    "steps": sum(1 for _, e, _ in d.steps if a <= e <= b)},
        "gc_longest_ms": 1e3 * max(gcs.pauses, default=0.0),
        "trace": None if red is None else {
            k: red[k] for k in ("window_s", "busy_s", "kernel_s",
                                "modules_s", "idle_by_span", "idle_gaps",
                                "device_ops")},
        "kernel_calls": None if red is None else {
            k: len(v) for k, v in red["kernels"].items()},
        "program": prog,
        "idle_split": (program_spans.idle_split(prog, per)
                       if prog else None),
        "scopes_s": program_spans.scopes_s(path, lo_ns, hi_ns),
        "long_calls": _under_long_calls(pd, raw, args.long_ms * 1e6,
                                        lo_ns, hi_ns),
    }
    os.makedirs(args.out, exist_ok=True)
    name = f"{args.workload}.{args.seed}"
    with open(os.path.join(args.out, name + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if args.keep_trace:
        with open(path, "rb") as f, \
                open(os.path.join(args.out, name + ".xplane.pb.xz"), "wb") as g:
            g.write(lzma.compress(f.read(), preset=1))
    shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps({k: result[k] for k in ("workload", "seed", "window",
                                             "stretch", "kernel_calls",
                                             "idle_split", "scopes_s")}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
