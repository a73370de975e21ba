"""Speculative decoding across the Whisper ladder (DESIGN.md §17; the
§5.1 E2E serving path spending tiny-model FLOPs to amortize base/small
steps — the ladder the paper's scaling study runs, §4.3).

A whisper-tiny-shaped draft proposes k tokens per round; the base/small
verifier scores the k+1 window in ONE jitted forward and greedy
acceptance keeps the stream token-exact with the verifier alone. The
gates, asserted every run (CI via ``--smoke`` on the default AND the
``REPRO_BACKEND=xla_ref`` matrix legs):

  - token-exact parity: for whisper-base AND whisper-small verifiers,
    dense f32 and q8_0+offload, the speculative token streams equal the
    verifier's own plain greedy ``transcribe`` exactly
  - speedup: speculative decode sustains > 1.5x the plain-greedy tok/s
    on both verifier rungs (draft acceptance via the echo workload below)
  - zero retraces: across the whole timed run the verify window, the
    draft step, and the plain-greedy step each compile exactly once
  - exact attribution: draft + verify ledger FLOPs (``by_role``) sum to
    the ledger's flop totals, and the per-round ledger spans claim every
    committed FLOP (the §16.2 integer invariant, checked by
    ``telemetry.ledger_consistent``)

Workload: the ladder is exercised at reduced scale (the real rungs'
relative step costs preserved — tiny ≪ base < small — with vocab shrunk
so the readout does not flatten the rung gap) with an *echo*
parameterization — decoder-block
output projections scaled by ``alpha`` so, with tied embeddings, every
rung's argmax approximately echoes its input token. Draft and verifier
then agree on most positions despite independent random init, giving the
high-acceptance regime the speedup gate needs; the parity gate is what
guards correctness and holds at ANY acceptance (the test suite drives
the near-zero-acceptance regime with raw random init).

Usage:
  PYTHONPATH=src python -m benchmarks.speculative [--smoke]

Writes experiments/bench/speculative.json.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List

# reduced ladder preserving the real rungs' *relative* step costs
# (tiny ≪ base < small; per-step FLOP ratios ~1:36:128), vocab 512
# (%16==0) so decode stays block-dominated and the draft/verifier gap
# survives the readout. The draft must be cheap not just in FLOPs but in
# *dispatch count* — verifier steps have to dominate wall-clock for the
# speculative trade to show, same as on real hardware where base/small
# steps are weight-streaming-bound (paper §4.3 coverage collapse).
_LADDER = {
    "tiny": dict(num_layers=1, num_encoder_layers=1, d_model=128,
                 num_heads=2, d_ff=512),
    "base": dict(num_layers=4, num_encoder_layers=4, d_model=384,
                 num_heads=6, d_ff=1536),
    "small": dict(num_layers=8, num_encoder_layers=8, d_model=512,
                  num_heads=8, d_ff=2048),
}


def _ladder_cfg(rung: str):
    from repro.configs.whisper_base import CONFIG

    s = _LADDER[rung]
    return dataclasses.replace(
        CONFIG, name=f"whisper-{rung}-ladder", vocab_size=512, vocab_pad=0,
        encoder_ctx=64, head_dim=64, num_kv_heads=s["num_heads"],
        dtype="float32", param_dtype="float32", remat="none",
        scan_layers=False, **s)


def _echo_params(params, alpha: float):
    """Scale every decoder-block output projection (self/cross attention
    ``o``, FFN ``down``) by ``alpha``: at small alpha the blocks approach
    identity, logits approach ``unembed(LN(embed(tok) + pos))``, and with
    tied embeddings each rung echoes its input token — the controllable
    high-acceptance workload (module docstring)."""
    import jax

    def scale(leaf_path):
        sub = params["dec_blocks"]
        for k in leaf_path:
            sub = sub[k]
        return jax.tree_util.tree_map(lambda a: a * alpha, sub)

    out = dict(params)
    blocks = dict(params["dec_blocks"])
    for arm, proj in (("self_attn", "o"), ("cross_attn", "o"),
                      ("ffn", "down")):
        blocks[arm] = dict(blocks[arm])
        blocks[arm][proj] = scale((arm, proj))
    out["dec_blocks"] = blocks
    return out


def _timed_greedy(engine, mel, max_new: int) -> Dict[str, object]:
    engine.transcribe(mel, max_new=max_new)            # compile warmup
    t0 = engine._step_traces
    res = engine.transcribe(mel, max_new=max_new)
    toks = sum(r.steps for r in res)
    wall = sum(r.decode_s for r in res)
    return {"tokens": [r.tokens for r in res], "toks": toks,
            "wall_s": wall, "tok_s": toks / max(wall, 1e-9),
            "retraces": engine._step_traces - t0}


def _timed_spec(spec, mel, max_new: int) -> Dict[str, object]:
    spec.transcribe(mel, max_new=max_new)              # compile warmup
    v0 = spec.verifier._verify_traces
    d0 = spec.draft._step_traces
    r0, dr0, a0 = spec.rounds, spec.drafted, spec.accepted
    res = spec.transcribe(mel, max_new=max_new)
    toks = sum(r.steps for r in res)
    wall = sum(r.decode_s for r in res)
    return {"tokens": [r.tokens for r in res], "toks": toks,
            "wall_s": wall, "tok_s": toks / max(wall, 1e-9),
            "rounds": spec.rounds - r0,
            "acceptance": (spec.accepted - a0) / max(spec.drafted - dr0, 1),
            "verify_retraces": spec.verifier._verify_traces - v0,
            "draft_retraces": spec.draft._step_traces - d0}


def _variant(rung: str, quant: str, tiny_cfg, tiny_params, mel,
             max_new: int, k: int, alpha: float) -> Dict[str, object]:
    import jax

    from repro import obs
    from repro.core.offload import OffloadEngine
    from repro.models import model as model_lib
    from repro.serve.engine import ServeEngine

    cfg = _ladder_cfg(rung)
    params = _echo_params(
        model_lib.init_params(jax.random.PRNGKey(1), cfg), alpha)
    off = (OffloadEngine() if quant == "q8_0" else None)
    tele = obs.Telemetry()
    v = ServeEngine(cfg, params, max_len=max_new + k + 1, quant=quant,
                    offload=off, eos_id=-1, telemetry=tele)
    greedy = _timed_greedy(v, mel, max_new)
    spec_engine = v.speculative(tiny_cfg, tiny_params, k=k)
    spec = _timed_spec(spec_engine, mel, max_new)

    checks = {
        "parity": greedy["tokens"] == spec["tokens"],
        "speedup": spec["tok_s"] > 1.5 * greedy["tok_s"],
        "zero_retrace": (greedy["retraces"] == 0
                         and spec["verify_retraces"] == 0
                         and spec["draft_retraces"] == 0),
    }
    report: Dict[str, object] = {}
    if off is not None:
        s = off.stats
        total = s.offloaded_flops + s.fallback_flops + s.residual_flops
        checks["by_role_sums"] = sum(s.by_role.values()) == total
        ledger = tele.ledger_consistent()
        checks["spans_exact"] = bool(ledger["exact"])
        report["by_role"] = dict(s.by_role)
        report["ledger"] = ledger
    return {"rung": rung, "quant": quant, "k": k,
            "greedy": {kk: vv for kk, vv in greedy.items()
                       if kk != "tokens"},
            "spec": {kk: vv for kk, vv in spec.items() if kk != "tokens"},
            "speedup_x": spec["tok_s"] / max(greedy["tok_s"], 1e-9),
            "checks": checks, "ok": all(checks.values()), **report}


def run(smoke: bool = False) -> dict:
    import jax
    import numpy as np

    from benchmarks.common import fmt_table, save
    from repro.models import model as model_lib

    b, max_new, k = (2, 24, 6) if smoke else (4, 48, 6)
    alpha = 0.02
    tiny_cfg = _ladder_cfg("tiny")
    tiny_params = _echo_params(
        model_lib.init_params(jax.random.PRNGKey(0), tiny_cfg), alpha)
    frames = 32
    mel = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                       (b, frames, tiny_cfg.n_mels)),
                     np.float32)

    variants: List[Dict[str, object]] = []
    for rung in ("base", "small"):
        for quant in ("none", "q8_0"):
            variants.append(_variant(rung, quant, tiny_cfg, tiny_params,
                                     mel, max_new, k, alpha))

    rows = []
    for v in variants:
        rows.append([v["rung"], v["quant"],
                     f"{v['greedy']['tok_s']:.1f}",
                     f"{v['spec']['tok_s']:.1f}",
                     f"{v['speedup_x']:.2f}x",
                     f"{v['spec']['acceptance']:.2f}",
                     str(v["spec"]["rounds"]),
                     "0" if v["checks"]["zero_retrace"] else "RETRACED"])
    print(f"speculative decoding, reduced ladder, tiny draft, k={k} "
          f"({'smoke' if smoke else 'full'})")
    print(fmt_table(rows, ["verifier", "quant", "greedy tok/s",
                           "spec tok/s", "speedup", "accept", "rounds",
                           "retraces"]))
    ok = True
    for v in variants:
        ok = ok and v["ok"]
        detail = " ".join(f"{kk}={'ok' if val else 'FAIL'}"
                          for kk, val in v["checks"].items())
        print(f"{v['rung']}/{v['quant']}: {detail} -> "
              f"{'ok' if v['ok'] else 'FAIL'}")
    out = {"smoke": smoke, "k": k, "alpha": alpha, "batch": b,
           "max_new": max_new, "variants": variants, "gate_ok": ok}
    save("speculative", out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for the CI gate")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke)
    return 0 if out["gate_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
