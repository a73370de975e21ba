"""Decode throughput: tokens/s for whisper-tiny greedy decode, engine-off
vs engine-on — the wall-clock proof of the plan/ledger refactor
(DESIGN.md §10).

Before the refactor, attaching an ``OffloadEngine`` forced the decode step
out of ``jax.jit`` (in-trace stats mutation made it impure), so the
paper's flagship configuration — Q8_0 dot products through the offload
dispatcher — was the *slowest* one this repo could run: every decode step
re-traced the whole decoder through op-by-op dispatch. After the split,
routing resolves at trace time, the step jits unconditionally, and
engine-on decode pays only its (identical-math) kernel cost.

Measured on the CI-class CPU container (whisper-tiny smoke config, B=2,
24 decode steps, XLA path both sides):

  pre-refactor  : engine-on ~33 tok/s (un-jitted op-by-op dispatch; the
                  penalty is unbounded — it grows with model depth since
                  every decode step re-dispatches every op)
  post-refactor : engine-off ~2546 tok/s, engine-on ~2389 tok/s —
                  ratio 1.07x, a ~78x engine-on speedup, comfortably
                  within the 2x acceptance bound; the residual gap is
                  the mixed-execution split's extra partial-sum adds

Usage:
  PYTHONPATH=src python -m benchmarks.decode_throughput [--smoke]

``--smoke`` shrinks the workload for the CI gate (it still exercises the
jitted engine-on path end to end, so a dispatch regression that breaks
jit-with-engine fails the workflow). Writes
experiments/bench/decode_throughput.json.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import fmt_table, save
from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine


def _decode_tok_s(engine: ServeEngine, mel: np.ndarray, max_new: int,
                  iters: int = 3) -> float:
    """Median decode tokens/s over ``iters`` transcribe calls (first call
    pays compilation; it is excluded by a warmup run)."""
    engine.transcribe(mel, max_new=max_new)             # warmup/compile
    rates = []
    for _ in range(iters):
        res = engine.transcribe(mel, max_new=max_new)
        toks = sum(r.steps for r in res)
        # rate uses the decode phase only so the (identical) encoder
        # prefill does not dilute the comparison
        dec = sum(r.decode_s for r in res)
        rates.append(toks / max(dec, 1e-9))
    rates.sort()
    return rates[len(rates) // 2]


def run(smoke: bool = False) -> dict:
    cfg = get_smoke_config("whisper-tiny")
    b, frames = (1, 8) if smoke else (2, 16)
    max_new = 6 if smoke else 24
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 64)
    mel = np.random.default_rng(0).standard_normal(
        (b, frames, cfg.n_mels)).astype(np.float32)

    off_engine = OffloadEngine(prefer_pallas=False)
    eng_on = ServeEngine(cfg, params, max_len=max_new + 8, quant="q8_0",
                         offload=off_engine, eos_id=-1)
    eng_off = ServeEngine(cfg, params, max_len=max_new + 8, quant="q8_0",
                          eos_id=-1)

    # median-of-3 in smoke mode too: the smoke decode window is ~ms-scale
    # and a single sample would make the CI ratio gate flake-prone
    iters = 3
    tok_s_off = _decode_tok_s(eng_off, mel, max_new, iters)
    tok_s_on = _decode_tok_s(eng_on, mel, max_new, iters)
    ratio = tok_s_off / max(tok_s_on, 1e-9)

    rows = [["engine-off", f"{tok_s_off:.1f}", "-"],
            ["engine-on", f"{tok_s_on:.1f}", f"{ratio:.2f}x"]]
    print("whisper-tiny decode throughput (tokens/s, jitted step both ways)")
    print(fmt_table(rows, ["config", "decode tok/s", "off/on ratio"]))
    within_2x = ratio <= 2.0
    print(f"engine-on within 2x of engine-off: {within_2x} "
          f"(plan/ledger split keeps the offloaded step jitted)")
    rep = eng_on.energy_report([])
    out = {"smoke": smoke, "batch": b, "frames": frames, "max_new": max_new,
           "tok_s_engine_off": tok_s_off, "tok_s_engine_on": tok_s_on,
           "off_on_ratio": ratio, "within_2x": within_2x,
           "dispatch": rep["dispatch"],
           "ledger": {"offloaded_calls": off_engine.stats.offloaded_calls,
                      "fallback_calls": off_engine.stats.fallback_calls,
                      "offload_rate": off_engine.stats.offload_rate()}}
    save("decode_throughput", out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for the CI benchmark-smoke gate")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke)
    # CI gate: a dispatch regression that un-jits the engine-on path shows
    # up as an extreme ratio (pre-refactor measured ~7x)
    return 0 if out["within_2x"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
