"""Mesh-sharded vs single-device continuous-batching serve (DESIGN.md §13;
the system-level scale-out the paper's companion CGLA evaluation — and the
ROADMAP's heavy-traffic north star — asks of the §5.1 E2E serving path).

The whole decode step runs as ONE sharded jitted program on a ≥2-device
mesh: the slot pool's slot axis shards over the mesh's "data" axis, the
Whisper weights replicate (data-only mesh — TP would reorder per-row
reductions and break bit-exactness), and admission splices into
device-local slot ranges. The gates, asserted every run (CI via
``--smoke`` on a forced 4-device host mesh,
``XLA_FLAGS=--xla_force_host_platform_device_count=4``):

  - token-exact parity: the sharded scheduler reproduces the
    single-device scheduler's per-request token streams for the same
    arrival trace, for dense bf16 AND q8_0+offload
  - zero step retraces: the sharded fixed-shape slot pool keeps the
    engine's ``step_fn`` at one trace across the whole schedule
  - exact per-device attribution: ``energy_report``'s
    ``dispatch.by_device`` sums to the ledger's total flop count
    (offloaded + fallback + residual), and every mesh device appears
  - plan-cache separation: sharded and unsharded engines at the same
    shapes hold disjoint plan keys (the mesh signature, DESIGN.md §13)

When launched on the CPU with fewer than 2 visible devices the benchmark
re-execs itself in a subprocess with the forced-host flag (jax pins the
device count at first init — same pattern as launch/dryrun.py). On an
accelerator with fewer than 2 devices it fails: a CPU result must never
be reported under the accelerator's name, and a child process could not
reach the chip this process already holds.

Per-request latency percentiles (p50/p95/p99) come from the shared
``obs.metrics`` histogram in exact (track_values) mode — the one
percentile implementation across serving benchmarks (DESIGN.md §16.3).

Usage:
  PYTHONPATH=src python -m benchmarks.sharded_serving [--smoke]

Writes experiments/bench/sharded_serving.json.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FORCE_FLAG = "--xla_force_host_platform_device_count=4"


def _reexec_forced(smoke: bool) -> dict:
    """Run this module in a subprocess with 4 forced host devices and load
    its JSON output (the current process's jax already pinned 1 device).
    The child pins ``JAX_PLATFORMS=cpu`` (the force flag only multiplies
    the *host* platform) and sets a sentinel so a child that still cannot
    see 2 devices fails instead of re-exec'ing forever."""
    if os.environ.get("_REPRO_SHARDED_REEXEC"):
        return {"smoke": smoke, "gate_ok": False,
                "error": "re-exec'd child still sees <2 devices"}
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " " + _FORCE_FLAG).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["_REPRO_SHARDED_REEXEC"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.sharded_serving"]
    if smoke:
        cmd.append("--smoke")
    cp = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                        text=True)
    sys.stdout.write(cp.stdout)
    sys.stderr.write(cp.stderr)
    out_path = os.path.join(ROOT, "experiments", "bench",
                            "sharded_serving.json")
    if cp.returncode == 0 and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    return {"smoke": smoke, "gate_ok": False,
            "error": f"forced-host subprocess exited {cp.returncode}"}


def _latency_summary(xs: List[float]) -> Dict[str, float]:
    """p50/p95/p99 through the ONE shared percentile implementation
    (repro.obs.metrics, DESIGN.md §16.3) in exact mode, matching the
    other serving benchmarks."""
    from repro.obs.metrics import LATENCY_BUCKETS_S, Histogram

    h = Histogram("latency_s", LATENCY_BUCKETS_S, track_values=True)
    for x in xs:
        h.observe(x)
    return {"p50_s": h.percentile(50), "p95_s": h.percentile(95),
            "p99_s": h.percentile(99)}


def _serve_trace(engine, mels: List, max_news: List[int], n_slots: int,
                 n_frames: int) -> Dict[str, object]:
    """Drive one engine's scheduler over the arrival trace; return token
    streams (keyed by submit order) and wall-clock busy time."""
    sched = engine.scheduler(n_slots=n_slots, n_frames=n_frames)
    rids = [sched.submit(m, max_new=mn) for m, mn in zip(mels, max_news)]
    t0 = time.perf_counter()
    got = sched.run()
    wall = time.perf_counter() - t0
    tokens = [got[r].tokens for r in rids]
    steps = sum(got[r].steps for r in rids)
    return {"tokens": tokens, "wall_s": wall, "steps": steps,
            "tok_s": steps / max(wall, 1e-9),
            "step_traces": sched.step_traces,
            **_latency_summary([got[r].total_s for r in rids]),
            # KV memory accounting (DESIGN.md §15.4)
            "kv_committed_bytes": sched.kv_committed_bytes,
            "kv_utilization": sched.kv_utilization_peak}


def _variant(name: str, cfg, params, quant: str, make_offload, mesh,
             smoke: bool) -> Dict[str, object]:
    import numpy as np

    from repro.serve.engine import ServeEngine

    n_slots = 4
    n_req, n_frames = (8, 16) if smoke else (16, 32)
    lo, hi = (3, 12) if smoke else (6, 24)
    rng = np.random.default_rng(0)
    mels = [rng.standard_normal((1, n_frames, cfg.n_mels)).astype(np.float32)
            for _ in range(n_req)]
    max_news = [int(rng.integers(lo, hi + 1)) for _ in range(n_req)]

    eng1 = ServeEngine(cfg, params, max_len=hi + 8, quant=quant,
                       offload=make_offload(), eos_id=-1)
    engm = ServeEngine(cfg, params, max_len=hi + 8, quant=quant,
                       offload=make_offload(), eos_id=-1, mesh=mesh)
    r1 = _serve_trace(eng1, mels, max_news, n_slots, n_frames)
    rm = _serve_trace(engm, mels, max_news, n_slots, n_frames)

    parity = r1["tokens"] == rm["tokens"]
    # one trace per engine total: the slot pool never changes shape, so
    # the whole schedule compiles the step exactly once (zero retraces)
    zero_retrace = r1["step_traces"] == 1 and rm["step_traces"] == 1

    checks = {"parity": parity, "zero_retrace": zero_retrace}
    report = {}
    if eng1.offload is not None:
        st = engm.offload.stats
        total = st.offloaded_flops + st.fallback_flops + st.residual_flops
        by_dev = engm.energy_report([])["dispatch"]["by_device"]
        n_mesh_dev = 1
        for a in mesh.axis_names:
            n_mesh_dev *= mesh.shape[a]
        checks["by_device_sums"] = sum(by_dev.values()) == total
        checks["all_devices_attributed"] = len(by_dev) == n_mesh_dev
        keys1 = set(eng1._plans.plans)
        keysm = set(engm._plans.plans)
        checks["plan_keys_disjoint"] = not (keys1 & keysm)
        report["by_device"] = by_dev
        report["ledger_flops"] = total
    ok = all(checks.values())
    return {"name": name, "single": {k: v for k, v in r1.items()
                                     if k != "tokens"},
            "sharded": {k: v for k, v in rm.items() if k != "tokens"},
            "checks": checks, "ok": ok, "n_req": n_req, "n_slots": n_slots,
            "n_frames": n_frames, **report}


def run(smoke: bool = False) -> dict:
    import jax

    if len(jax.devices()) < 2:
        if jax.default_backend() != "cpu":
            return {"smoke": smoke, "gate_ok": False,
                    "error": f"sharded serving needs >=2 "
                             f"{jax.default_backend()} devices, have "
                             f"{len(jax.devices())}"}
        return _reexec_forced(smoke)

    import jax.random  # noqa: F401

    from benchmarks.common import fmt_table, save
    from repro.configs.registry import get_config, get_smoke_config
    from repro.core.offload import OffloadEngine
    from repro.launch.mesh import make_serve_mesh
    from repro.models import model as model_lib

    cfg = get_smoke_config("whisper-tiny") if smoke \
        else get_config("whisper-tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 448)
    mesh = make_serve_mesh()          # data-only: bit-exact parity

    variants = [
        _variant("dense", cfg, params, "none", lambda: None, mesh, smoke),
        _variant("q8_0+offload", cfg, params, "q8_0",
                 lambda: OffloadEngine(prefer_pallas=False),
                 mesh, smoke),
    ]

    rows = []
    for v in variants:
        for mode in ("single", "sharded"):
            r = v[mode]
            rows.append([v["name"], mode, f"{r['tok_s']:.1f}",
                         f"{r['p50_s']*1e3:.0f}", f"{r['p95_s']*1e3:.0f}",
                         f"{r['p99_s']*1e3:.0f}",
                         str(r["steps"]), str(r["step_traces"]),
                         f"{r['kv_committed_bytes']/1024:.0f}",
                         f"{r['kv_utilization']:.2f}"])
    n_dev = len(jax.devices())
    print(f"whisper-tiny sharded serving on a {n_dev}-device host mesh "
          f"({'smoke' if smoke else 'full'} config)")
    print(fmt_table(rows, ["variant", "mode", "tok/s", "p50(ms)", "p95(ms)",
                           "p99(ms)", "steps", "traces",
                           "KV committed(KiB)", "KV util"]))
    ok = True
    for v in variants:
        ok = ok and v["ok"]
        detail = " ".join(f"{k}={'ok' if val else 'FAIL'}"
                          for k, val in v["checks"].items())
        print(f"{v['name']}: {detail} -> {'ok' if v['ok'] else 'FAIL'}")
    out = {"smoke": smoke, "n_devices": n_dev,
           "mesh": [[a, int(mesh.shape[a])] for a in mesh.axis_names],
           "variants": variants, "gate_ok": ok}
    save("sharded_serving", out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for the CI gate")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke)
    if "error" in out:
        print(f"sharded_serving: {out['error']}", file=sys.stderr)
    return 0 if out["gate_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
