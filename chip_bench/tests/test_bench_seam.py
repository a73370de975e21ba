"""A configuration of another model family is added by files alone: in a
copy of the benchmark, a test writes a decoder-only configuration (the
registry's qwen2.5-14b at its smoke widths, family ``decoder_lm``), a
token backlog mix and a token Poisson mix, and a workload file for each,
then drives the whole of ``harness.run_cell`` on the CPU (the look for a
chip is ``main``'s). Sound runs are ``correct``; a run with the served
path broken underneath is not, and the int4 control fails the limit that
sound runs meet. No file that the benchmark has is edited, and nothing is
written outside the test's temporary directory."""
from __future__ import annotations

import json
import os
import shutil
import time

import pytest

from chip_bench import harness, spec
from test_bench_faults import alter_tokens, freeze_state
from test_bench_spec import _digest

ROOT, BENCH = spec.ROOT, spec.BENCH_DIR
CONFIG = "qwen2.5-14b-smoke"
#: the logit-gap limit of these cells, near the geometric mean of the
#: readings: sound runs' widest gap 0-0.086 (bf16 activations), the int4
#: control's 1.04-2.06 (seeds 1-6, both mixes, on the CPU)
GAP = 0.3

FILES = {
    f"configs/{CONFIG}.json": {
        "name": CONFIG, "arch": "qwen2.5-14b", "family": "decoder_lm",
        "quant": "q8_0",
        "source": "https://huggingface.co/Qwen/Qwen2.5-14B/blob/main/"
                  "config.json",
        "published": {
            "hidden_size": 5120, "num_hidden_layers": 48,
            "num_attention_heads": 40, "num_key_value_heads": 8,
            "intermediate_size": 13824, "vocab_size": 152064,
            "rope_theta": 1000000.0, "tie_word_embeddings": False,
            "hidden_act": "silu", "rms_norm_eps": 1e-06,
            "max_position_embeddings": 131072},
        "reduced": [],
        # the CPU's size: 2 layers at d 64, 4 query heads on 1 k/v head
        "smoke": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                  "scan_layers": True, "vocab_size": 500, "vocab_pad": 12},
        "deployment": {"n_slots": 4, "max_len": 32}},
    "traffic/prompts.json": {
        "kind": "backlog",
        "prompt_tokens": {"dist": "uniform", "low": 6, "high": 7,
                          "integer": True},
        "out_tokens": {"dist": "uniform", "low": 3, "high": 8,
                       "integer": True},
        "depth_per_slot": 2, "table": 16},
    "traffic/chat.json": {
        "kind": "poisson", "rate_per_s": 8.0,
        "prompt_tokens": {"dist": "uniform", "low": 6, "high": 7,
                          "integer": True},
        "out_tokens": {"dist": "uniform", "low": 3, "high": 8,
                       "integer": True}},
    f"workloads/{CONFIG}.prompts.json": {
        "traffic": {"expected_tokens_per_s": 300},
        "limits": {"max_logit_gap": GAP, "sample_tokens": 60,
                   "sample_requests": 8, "pad_tokens": 8},
        "trace": {"seconds": 0.3}},
    f"workloads/{CONFIG}.chat.json": {
        "limits": {"max_logit_gap": GAP, "sample_tokens": 60,
                   "sample_requests": 8, "pad_tokens": 8},
        "trace": {"seconds": 0.3}},
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the new files written and the new
    cells listed; returns (spec, bench dir, the digests of the files the
    copy had before)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "chip_bench")
    for rel, body in FILES.items():
        path = root / "chip_bench" / rel
        assert not path.exists(), rel
        path.write_text(json.dumps(body))
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["configs"].append({"name": CONFIG, "source": FILES[
        f"configs/{CONFIG}.json"]["source"],
        "file": f"chip_bench/configs/{CONFIG}.json", "reduced": [],
        "why": "a test configuration"})
    for mix in ("prompts", "chat"):
        s["workloads"].append({"name": f"{CONFIG}.{mix}", "config": CONFIG,
                               "traffic": mix, "chips": 1,
                               "why": "a test cell"})
    return s, str(root / "chip_bench"), before


def run(checkout, mix, seed, **kw):
    s, bench, _ = checkout
    cell = spec.resolve(s, f"{CONFIG}.{mix}", bench_dir=bench)
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                            **kw)


def alter_first(engine):
    """The prefill's pick after the prompt replaced by its neighbour."""
    pick = engine.first_token

    def broken(out, sot_id):
        return (pick(out, sot_id) + 1) % engine.cfg.vocab_size
    engine.first_token = broken


@pytest.mark.parametrize("mix", ["prompts", "chat"])
def test_sound_run_is_correct(checkout, mix):
    out = run(checkout, mix, 2**31 + 77)
    res = out["result"]
    assert res["correct"], out["lines"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= GAP
    assert "programs compiled inside the window: 0" in out["lines"]
    # each request's first served token is its prefill's pick
    assert all(r.first is not None for r in out["run"].requests.values()
               if r.tokens)
    _, bench, before = checkout
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        rel.replace("/", os.sep) for rel in FILES}


@pytest.mark.parametrize("fault", [alter_tokens, freeze_state, alter_first],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(checkout, fault):
    res = run(checkout, "prompts", 2**31 + 78, engine_hook=fault)["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > GAP


@pytest.mark.parametrize("seed", [2, 3])
def test_int4_control_fails_the_limit(checkout, seed):
    out = run(checkout, "chat", seed, control_bits=4)
    served = max(float(g.max()) for g in out["served_gaps"])
    control = max(float(g.max()) for g in out["control_gaps"])
    assert served <= GAP < control
    assert out["result"]["correct"]
    assert out["control"]["correct"] is False


def test_published_sizes_are_checked(checkout):
    conf = dict(FILES[f"configs/{CONFIG}.json"])
    del conf["smoke"]
    _, bench, _ = checkout
    cfg = spec.model_config(conf, bench)
    assert (cfg.d_model, cfg.num_layers, cfg.quant) == (5120, 48, "q8_0")
    conf["published"] = dict(conf["published"], num_key_value_heads=4)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        spec.model_config(conf, bench)
