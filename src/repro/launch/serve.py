"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the ServeEngine with the paper's Q8_0 offload path and runs a batch
of synthetic requests, reporting latency + PDP/EDP per request (the
paper's Table 5 / Fig 9 quantities under the TDP-normalized power model).

``--continuous`` serves the same requests through the slot-pool
continuous-batching scheduler instead (DESIGN.md §11): staggered
admission into a fixed-width slot batch, per-request eviction, streamed
tokens, and exact per-request ledger/PDP attribution. ``--mesh`` serves
sharded over every visible device (DESIGN.md §13): slot-DP over the
data axis, per-device FLOP attribution in the energy report.

``--speculative`` serves the batch through a two-model speculative
engine (DESIGN.md §17): a cheap draft arch (``--draft``, default
whisper-tiny) proposes ``-k`` tokens per round, the main arch verifies
the window in one forward, and the consolidated report gains the
acceptance rate plus the draft/verify PDP split from the shared ledger.

``--trace-out``/``--metrics-out`` attach the observability subsystem
(DESIGN.md §16): either flag enables telemetry, the run's lifecycle
trace lands as Perfetto ``trace_event`` JSON (open at
https://ui.perfetto.dev), the metrics as Prometheus text exposition, and
the launcher prints ONE consolidated JSON report — energy, per-request
attribution (PDP, queue wait, TTFT), and the telemetry snapshot with its
§16.2 ledger-consistency record — instead of scattered summary lines.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import obs
from repro.configs.registry import ALL_ARCHS, get_config, get_smoke_config
from repro.core.offload import OffloadEngine
from repro.launch import compile_cache
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ALL_ARCHS))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant", default="q8_0", choices=["none", "q8_0"])
    ap.add_argument("--offload", action="store_true",
                    help="route GEMMs through the offload dispatcher")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching scheduler (DESIGN.md §11) "
                         "instead of one static batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool width for --continuous")
    ap.add_argument("--mesh", action="store_true",
                    help="serve sharded over all visible devices "
                         "(DESIGN.md §13; combine with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding (DESIGN.md §17): draft with "
                         "a cheap ladder model, verify with --arch")
    ap.add_argument("--draft", default="whisper-tiny",
                    choices=sorted(ALL_ARCHS),
                    help="draft arch for --speculative")
    ap.add_argument("-k", type=int, default=6,
                    help="draft window size for --speculative")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's Perfetto trace_event JSON here "
                         "(enables telemetry, DESIGN.md §16)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus text exposition here "
                         "(enables telemetry, DESIGN.md §16)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.speculative and args.continuous:
        ap.error("--speculative uses its own wave batching "
                 "(DESIGN.md §17.4); drop --continuous")
    if args.speculative and args.mesh:
        ap.error("--speculative over a sharded mesh is not supported yet")

    compile_cache.enable()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg,
                                   max_positions=512)
    # platform defaults: main segments resolve to the native Pallas
    # kernels on a TPU and to xla_ref elsewhere (DESIGN.md §12.2)
    offload = OffloadEngine() if args.offload else None
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh()
        print(f"serving mesh: {dict(mesh.shape)} over "
              f"{len(jax.devices())} device(s)")
    telemetry = (obs.Telemetry()
                 if (args.trace_out or args.metrics_out) else None)
    engine = ServeEngine(cfg, params, max_len=args.max_new + 32,
                         quant=args.quant, offload=offload, mesh=mesh,
                         telemetry=telemetry)

    rng = np.random.default_rng(args.seed)
    # --full serves the published 30 s encoder window
    n_frames = cfg.encoder_ctx if args.full else 64
    batch = engine.family.random_batch(cfg, rng, args.requests, n_frames)
    payloads = [batch[i:i + 1] for i in range(args.requests)]

    attribution = None
    if args.continuous:
        sched = engine.scheduler(n_slots=args.slots, n_frames=n_frames)
        rids = [sched.submit(p, max_new=args.max_new) for p in payloads]
        streamed = {r: 0 for r in rids}

        def on_token(ev):
            streamed[ev.rid] += 1

        # drive the drain manually so attribution() sees the finished
        # (unclaimed) results — run() would claim them first
        while sched.n_queued or sched.n_active:
            sched.admit()
            for ev in sched.decode_step():
                on_token(ev)
        attribution = sched.attribution()
        got = sched.run(on_token=on_token)             # claims results
        results = [got[r] for r in rids]
        print(f"continuous batching: {args.slots} slots, "
              f"{sum(streamed.values())} tokens streamed, "
              f"{sched.step_traces} step trace(s)")
    elif args.speculative:
        if "verify" not in engine.family.serves:
            ap.error("--speculative serves the Whisper ladder "
                     "(audio archs, DESIGN.md §17)")
        dcfg = (get_config(args.draft) if args.full
                else get_smoke_config(args.draft))
        dparams = model_lib.init_params(jax.random.PRNGKey(args.seed + 1),
                                        dcfg, max_positions=512)
        spec = engine.speculative(dcfg, dparams, k=args.k)
        results = spec.transcribe(batch, max_new=args.max_new)
        print(f"speculative: draft={args.draft} k={args.k} "
              f"acceptance={spec.acceptance_rate():.2f} "
              f"rounds={spec.rounds} "
              f"verify_traces={spec.stats()['verify_traces']}")
    else:
        results = engine.generate(batch, max_new=args.max_new)

    for i, r in enumerate(results):
        print(f"req{i}: {r.steps} tokens in {r.total_s:.3f}s "
              f"(prefill {r.prefill_s:.3f}s) pdp={r.pdp_j():.1f}J "
              f"tokens={r.tokens[:8]}...")
    # ONE consolidated report (DESIGN.md §16): energy + per-request
    # attribution (PDP / queue wait / TTFT) + the telemetry snapshot,
    # instead of the scattered ledger/plan-cache summary lines
    report = {"energy": engine.energy_report(results)}
    if attribution is not None:
        report["attribution"] = attribution
    if args.speculative:
        # acceptance + the draft/verify FLOP split (DESIGN.md §17.3);
        # energy_report's dispatch.by_role carries the same split scaled
        # into the PDP attribution when --offload is on
        report["speculative"] = spec.stats()
    if telemetry is not None:
        report["telemetry"] = telemetry.snapshot()
        if args.trace_out:
            print("trace written:", telemetry.write_trace(args.trace_out))
        if args.metrics_out:
            print("metrics written:",
                  telemetry.write_metrics(args.metrics_out))
    print(json.dumps(report, indent=1, default=str, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
