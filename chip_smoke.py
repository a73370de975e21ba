#!/usr/bin/env python3
"""Chip smoke test: the Whisper-tiny Q8_0 offload serving path on a TPU.

    python3 chip_smoke.py                # one chip: the main serving path
    python3 chip_smoke.py --four-chips   # slot-DP sharded serving, 4 chips

One chip: whisper-tiny at its published widths, weights drawn from
``--seed``, Q8_0 weights with the offload engine attached at platform
defaults. Four requests of 1500 mel frames (the 30 s encoder window) and
16 new tokens each go through the continuous-batching scheduler, the path
``python -m repro.launch.serve --continuous`` serves. The checks:

  * from the offload ledger: main segments ran on ``pallas_tpu`` with
    interpret off, ``q8_matmul`` in prefill and ``q8_matvec`` in decode,
    and the compiled programs hold Mosaic kernels (``tpu_custom_call``);
  * how ``dec.vocab`` (the tied readout) was routed: where its plan entry
    offloads it, it must be on ``pallas_tpu``;
  * on the same chip, prefill logits and the first decode steps against
    the same configuration under ``REGISTRY.force("xla_ref")``: the
    largest logit difference within ``LOGIT_RTOL`` of the largest logit,
    and the same argmax token.

``--four-chips`` runs only the sharded path: the same seeded trace through
the single-device scheduler and through one sharded over a 4-chip
``make_serve_mesh()``, which must give the same tokens, with
``energy_report()["dispatch"]["by_device"]`` naming all 4 devices.

Timings printed here are smoke timings of a single run, not benchmark
results. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
it is printed only when every check passed. Without a TPU, or when a
check fails, the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.backends import REGISTRY, platform                # noqa: E402
from repro.configs.registry import get_config                # noqa: E402
from repro.core.offload import OffloadEngine                 # noqa: E402
from repro.launch import compile_cache                       # noqa: E402
from repro.models import model as model_lib                  # noqa: E402
from repro.serve.engine import ServeEngine                   # noqa: E402

ARCH = "whisper-tiny"
N_REQUESTS = 4
MAX_NEW = 16
N_COMPARE = 4            # decode steps compared against xla_ref
SOT = 1
# Both paths compute the same Q8_0 math, but not with the same roundings:
# on a TPU, XLA's default precision for an f32 matmul rounds its operands
# to bf16 (8 significant bits, 2^-8 relative), Mosaic's kernels round
# differently, and the difference compounds through 4 encoder and 4
# decoder layers of dependent matmuls. 2e-2 of the largest logit allows a
# handful of such roundings; a wrong tile or a misplaced block scale
# shifts logits by the order of the logits themselves.
LOGIT_RTOL = 2e-2


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def build_engine(cfg, params, mesh=None) -> ServeEngine:
    # eos off: the weights are random, so EOS means nothing, and a fixed
    # token count keeps the work of every run the same
    return ServeEngine(cfg, params, max_len=MAX_NEW + 8, quant="q8_0",
                       offload=OffloadEngine(), eos_id=None, mesh=mesh)


def make_mels(cfg, rng, n: int):
    return [rng.standard_normal((cfg.encoder_ctx, cfg.n_mels))
            .astype(np.float32) for _ in range(n)]


def serve(engine: ServeEngine, mels, max_news, n_slots: int):
    """Drive the continuous-batching scheduler over one trace. Returns the
    token streams and results in submit order, the scheduler, the number
    of decode steps, and the ledger's per-backend call deltas of the
    admissions (prefill) and of the decode steps."""
    sched = engine.scheduler(n_slots=n_slots, n_frames=engine.cfg.encoder_ctx)
    rids = [sched.submit(m, max_new=n) for m, n in zip(mels, max_news)]
    stats = engine.offload.stats
    calls = {"prefill": Counter(), "decode": Counter()}

    def counted(phase, fn):
        before = Counter(stats.by_backend)
        out = fn()
        calls[phase].update(Counter(stats.by_backend) - before)
        return out

    steps = 0
    t0 = time.perf_counter()
    while sched.n_queued or sched.n_active:
        counted("prefill", sched.admit)
        steps += bool(counted("decode", sched.decode_step))
    wall = time.perf_counter() - t0
    got = sched.run()
    return {"tokens": [got[r].tokens for r in rids],
            "results": [got[r] for r in rids], "sched": sched,
            "steps": steps, "wall_s": wall, **calls}


def plan_calls(plan, times: int) -> Counter:
    """(backend, kernel) -> calls for ``times`` executions of a plan."""
    c = Counter()
    for e in plan:
        c[(e.backend, e.kernel)] += times
    return c


def by_backend(calls: Counter) -> Counter:
    out = Counter()
    for (backend, _), n in calls.items():
        out[backend] += n
    return out


def logits_trace(engine: ServeEngine, mel, tokens):
    """Batch-1 prefill, then decode ``tokens`` in order; returns the
    (len(tokens), vocab) logits and the encoder memory."""
    v = engine.cfg.vocab_size
    memory, state = engine._prefill_jit(engine._serve_params,
                                        jnp.asarray(mel[None]))
    out = []
    for t in tokens:
        logits, state = engine._decode_jit(
            engine._serve_params, jnp.full((1, 1), t, jnp.int32), state)
        out.append(np.asarray(logits[0, -1, :v], np.float32))
    return np.stack(out), np.asarray(memory, np.float32)


def one_chip(cfg, params, rng) -> None:
    engine = build_engine(cfg, params)
    mels = make_mels(cfg, rng, N_REQUESTS)

    # -- warm-up: compiles batch-1 prefill and the slot-batched step --------
    t0 = time.perf_counter()
    serve(engine, mels[:1], [2], n_slots=N_REQUESTS)
    print(f"smoke timing (not a benchmark result): compile + first "
          f"request {time.perf_counter() - t0:.3f} s")

    # -- the served trace ----------------------------------------------------
    r = serve(engine, mels, [MAX_NEW] * N_REQUESTS, n_slots=N_REQUESTS)
    for i, res in enumerate(r["results"]):
        print(f"smoke timing (not a benchmark result): request {i}: "
              f"{res.steps} tokens, {res.total_s:.4f} s "
              f"(prefill {res.prefill_s:.4f} s)")
    print(f"smoke timing (not a benchmark result): {N_REQUESTS} requests "
          f"served in {r['wall_s']:.3f} s")
    check(all(len(t) == MAX_NEW for t in r["tokens"]),
          f"expected {MAX_NEW} tokens per request, got "
          f"{[len(t) for t in r['tokens']]}")
    check(r["sched"].step_traces == 1,
          f"decode step traced {r['sched'].step_traces} times, expected 1")

    # -- routing, from the ledger and the recorded plans --------------------
    prefill_plan = engine._plans.plans[
        engine._key("prefill", 1, cfg.encoder_ctx)]
    step_plan = r["sched"]._step_plan
    pre = plan_calls(prefill_plan, N_REQUESTS)
    dec = plan_calls(step_plan, r["steps"])
    for phase, calls in (("prefill", pre), ("decode", dec)):
        ledger = r[phase]
        print(f"{phase}: ledger by_backend {dict(sorted(ledger.items()))}; "
              f"by (backend, kernel) "
              f"{ {f'{b}/{k}': n for (b, k), n in sorted(calls.items())} }")
        check(by_backend(calls) == ledger,
              f"{phase}: plan calls {dict(by_backend(calls))} disagree with "
              f"the ledger {dict(ledger)}")
    check(pre[("pallas_tpu", "q8_matmul")] > 0,
          "prefill ran no q8_matmul main segment on pallas_tpu")
    check(dec[("pallas_tpu", "q8_matvec")] > 0,
          "decode ran no q8_matvec main segment on pallas_tpu")
    interpret = platform.default_interpret() or bool(engine.offload.interpret)
    print(f"pallas interpret mode: {'on' if interpret else 'off'}")
    check(not interpret, "Pallas kernels would run in interpret mode")

    for e in (e for e in step_plan if e.name == "dec.vocab"):
        how = (f"offloaded to {e.backend} ({e.kernel}, tiling "
               f"{e.tiling or 'default'}, main K {e.k_main} of {e.k})"
               if e.offload else
               f"coverage fallback to {e.backend} (the paper's host path "
               f"for an invocation over the VMEM budget)")
        print(f"dec.vocab (m={e.m}, k={e.k}, n={e.n}): {how}")
        check(not e.offload or e.backend == "pallas_tpu",
              f"dec.vocab offloaded to {e.backend}, not pallas_tpu")

    # -- the same chip, the same math, on xla_ref ----------------------------
    # a separate engine of the same configuration: the jitted functions of
    # one engine cache their first trace, so one engine cannot hold both
    # routings at the same shapes
    ref = build_engine(cfg, params)
    tokens = [SOT]
    with REGISTRY.force("xla_ref"):
        for _ in range(N_COMPARE):
            lx, mem_x = logits_trace(ref, mels[0], tokens)
            tokens.append(int(np.argmax(lx[-1])))
    tokens = tokens[:N_COMPARE]
    lp, mem_p = logits_trace(engine, mels[0], tokens)
    diff = float(np.max(np.abs(lp - lx)))
    tol = LOGIT_RTOL * float(np.max(np.abs(lx)))
    print(f"encoder memory max |pallas - xla_ref| = "
          f"{float(np.max(np.abs(mem_p - mem_x))):.6g}")
    print(f"logits over prefill + {N_COMPARE - 1} decode steps: max "
          f"|pallas - xla_ref| = {diff:.6g}, tolerance {tol:.6g} "
          f"({LOGIT_RTOL} x max |logit| {float(np.max(np.abs(lx))):.6g})")
    check(diff <= tol, f"logit difference {diff} exceeds {tol}")
    tp, tx = lp.argmax(-1), lx.argmax(-1)
    ties = 0
    for step, (a, b) in enumerate(zip(tp, tx)):
        if a != b:
            # two tokens within the tolerance on the reference are a tie
            # the roundings may break either way, not a disagreement
            gap = float(lx[step, b] - lx[step, a])
            check(gap <= tol, f"step {step}: argmax {a} vs xla_ref {b}, "
                              f"reference gap {gap} > {tol}")
            ties += 1
    print(f"argmax tokens: pallas {tp.tolist()} xla_ref {tx.tolist()} "
          f"({N_COMPARE - ties}/{N_COMPARE} equal, {ties} within-tolerance "
          f"ties)")

    for name, fn, args in (
            ("prefill", engine._prefill_jit,
             (engine._serve_params, jnp.asarray(mels[0][None]))),
            ("decode step", engine._step_jit,
             (engine._serve_params, jnp.zeros((N_REQUESTS, 1), jnp.int32),
              jnp.zeros((N_REQUESTS,), bool), r["sched"].pool.state))):
        check("tpu_custom_call" in fn.lower(*args).as_text(),
              f"the compiled {name} holds no Mosaic kernel")
    print("compiled prefill and decode step hold Mosaic kernels "
          "(tpu_custom_call)")


def four_chips(cfg, params, rng) -> None:
    from repro.launch.mesh import make_serve_mesh

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--four-chips needs 4 devices, found {n_dev}")
    mesh = make_serve_mesh()
    print(f"serving mesh {dict(mesh.shape)}")
    n_req = 2 * n_dev
    mels = make_mels(cfg, rng, n_req)
    max_news = [int(x) for x in rng.integers(MAX_NEW // 2, MAX_NEW + 1,
                                             n_req)]
    single = serve(build_engine(cfg, params), mels, max_news, n_slots=n_dev)
    sharded_engine = build_engine(cfg, params, mesh=mesh)
    sharded = serve(sharded_engine, mels, max_news, n_slots=n_dev)
    print(f"smoke timing (not a benchmark result): single device "
          f"{single['wall_s']:.3f} s, sharded {sharded['wall_s']:.3f} s "
          f"for {n_req} requests (compilation included)")
    same = [a == b for a, b in zip(single["tokens"], sharded["tokens"])]
    print(f"token parity with the single-device scheduler: "
          f"{sum(same)}/{n_req} requests")
    check(all(same), "sharded tokens differ from the single-device "
                     "scheduler's")
    for phase in ("prefill", "decode"):
        print(f"sharded {phase}: ledger by_backend "
              f"{dict(sorted(sharded[phase].items()))}")
    check(sharded["decode"]["pallas_tpu"] > 0,
          "sharded decode ran no main segment on pallas_tpu")
    by_dev = sharded_engine.energy_report([])["dispatch"]["by_device"]
    print(f"by_device: {by_dev}")
    check(len(by_dev) == n_dev, f"by_device names {len(by_dev)} devices, "
                                f"expected {n_dev}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the slot-DP sharded serving path over "
                         "4 chips and its single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")
    print(f"compile cache: {compile_cache.enable()}")

    cfg = get_config(ARCH)
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg,
                                   max_positions=448)
    rng = np.random.default_rng(args.seed)
    print(f"{ARCH}: d_model={cfg.d_model} layers={cfg.num_encoder_layers}+"
          f"{cfg.num_layers} vocab={cfg.vocab_size} frames="
          f"{cfg.encoder_ctx}, Q8_0 offload, seed {args.seed}")
    (four_chips if args.four_chips else one_chip)(cfg, params, rng)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
