"""END-TO-END DRIVER (the paper's kind is inference): serve the FULL
whisper-tiny configuration with batched requests through the Q8_0 offload
path, reporting per-request latency and PDP/EDP — the deployment the paper
targets, on the TPU-native stack.

  PYTHONPATH=src python examples/serve_whisper.py [--requests 4] [--dense]
                                                  [--stream]

Flow per the paper's Fig 1: mel frames -> encoder (once per utterance) ->
per-layer cross-K/V projection (dec.cross.kv) -> autoregressive greedy
decode against the self-attention KV cache. Every GEMM routes through the
offload dispatcher: main segments on the native Pallas kernels on a TPU
(the same math on xla_ref elsewhere), residuals on the host path, with
coverage-based fallback.

``--stream`` serves the same utterances through the continuous-batching
scheduler (DESIGN.md §11) instead: requests are submitted STAGGERED —
half up front, the rest arriving while earlier utterances are mid-decode
— admitted into freed slots of the fixed-shape KV pool between jitted
steps, and each token prints the moment its request produces it.
"""
import argparse
import os
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.core import energy
from repro.core.offload import OffloadEngine
from repro.launch import compile_cache
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine
from repro.tuning import Autotuner


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--frames", type=int, default=192,
                    help="mel frames per utterance (1500 = full 30s window)")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--dense", action="store_true",
                    help="FP16/bf16 baseline instead of Q8_0")
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching scheduler with staggered "
                         "submission + per-token streaming (DESIGN.md §11)")
    ap.add_argument("--slots", type=int, default=2,
                    help="slot-pool width for --stream")
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config("whisper-tiny")
    print(f"whisper-tiny: {cfg.n_params()/1e6:.1f}M params, "
          f"{cfg.num_encoder_layers}+{cfg.num_layers} layers, "
          f"d={cfg.d_model}, vocab={cfg.vocab_size}")

    t0 = time.time()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 448)
    print(f"init {time.time()-t0:.1f}s")

    quant = "none" if args.dense else "q8_0"
    # Autotuned dispatch (DESIGN.md §9): ServeEngine pre-tunes the whisper
    # GEMM shapes at construction and persists winners for later runs.
    tuner = Autotuner(cache_path=os.path.join("experiments", "tuning",
                                              "whisper_tiny.json"),
                      mode="analytic")
    # platform defaults: native Pallas kernels on a TPU, xla_ref elsewhere
    offload = OffloadEngine(vmem_budget_kb=8 * 1024, burst=128, tuner=tuner)
    engine = ServeEngine(cfg, params, max_len=args.max_new + 8,
                         quant=quant, offload=offload, eos_id=-1)

    rng = np.random.default_rng(0)
    mel = rng.standard_normal(
        (args.requests, args.frames, cfg.n_mels)).astype(np.float32)

    if args.stream:
        # Continuous batching (DESIGN.md §11): half the utterances are
        # queued up front; the rest are submitted between decode steps —
        # they land in slots freed by earlier evictions while the batch
        # keeps stepping, and every token streams as soon as it exists.
        sched = engine.scheduler(n_slots=args.slots, n_frames=args.frames)
        half = max(1, args.requests // 2)
        rids = [sched.submit(mel[i:i + 1], max_new=args.max_new)
                for i in range(half)]
        late = list(range(half, args.requests))
        print(f"\nstreaming {args.requests} utterances through "
              f"{args.slots} slots ({half} queued, {len(late)} arriving "
              f"mid-decode, {quant} path)...")

        def on_token(ev):
            print(f"  [stream] utt{ev.rid} step {ev.step}: token "
                  f"{ev.token}{'  <eos/budget>' if ev.done else ''}")

        while sched.n_queued or sched.n_active or late:
            sched.admit()
            for ev in sched.decode_step():
                on_token(ev)
            if late:                      # staggered arrival mid-decode
                i = late.pop(0)
                rids.append(sched.submit(mel[i:i + 1],
                                         max_new=args.max_new))
                print(f"  [arrive] utt{rids[-1]} submitted mid-decode")
        got = sched.finished
        results = [got[r] for r in rids]
        print(f"zero retraces after warmup: "
              f"{sched.step_traces} step trace(s) total")
    else:
        print(f"\ntranscribing {args.requests} utterances "
              f"({args.frames} frames each, {quant} path)...")
        results = engine.transcribe(mel, max_new=args.max_new)
    for i, r in enumerate(results):
        print(f"  utt{i}: {r.steps} tokens | prefill {r.prefill_s:.2f}s "
              f"decode {r.decode_s:.2f}s | PDP {r.pdp_j():.1f} J "
              f"(v5e TDP model)")

    rep = engine.energy_report(results)
    st = offload.stats
    print(f"\nbatch: {rep['requests']} reqs, {rep['total_s']:.2f}s total, "
          f"PDP {rep['pdp_j']:.1f} J, EDP {rep['edp_js']:.1f} J*s")
    print(f"offload: {st.offloaded_calls} offloaded / {st.fallback_calls} "
          f"fallback calls ({st.offload_rate():.1%} — paper: 93.8% coverage "
          f"at 32KB); flop offload rate {st.offload_flop_rate():.1%}")
    print(f"by kernel class: { {k: v for k, v in sorted(st.by_kernel.items())[:8]} }")


if __name__ == "__main__":
    main()
