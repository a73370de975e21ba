"""The benchmark's files agree with each other and with its contract:
names, units, metric wiring, and that new cells, mixes and metrics are
found by name from new files alone."""
from __future__ import annotations

import functools
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_cells import WHISPER
from chip_bench import spec, traffic
from repro.configs.registry import get_config

ROOT = spec.ROOT
BENCH = spec.BENCH_DIR
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = spec.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
#: 80-channel mel requests, as whisper-tiny and whisper-small take them
MEL80 = functools.partial(WHISPER.payload, cfg=get_config("whisper-tiny"))


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chip_bench"]
    assert SPEC["command"] == ["python3", "chip_bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    n = 24                              # a full check at the most cells
    assert (2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell):
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    confs = {c["name"]: c for c in SPEC["configs"]}
    assert w["config"] in confs
    assert os.path.isfile(os.path.join(ROOT, confs[w["config"]]["file"]))
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       f"{w['traffic']}.json"))
    resolved = spec.resolve(SPEC, cell)
    cfg = spec.model_config(resolved["config"])
    assert resolved["config"]["name"] == w["config"]
    assert resolved["limits"]["pad_tokens"] >= \
        resolved["traffic"]["out_tokens"]["high"]
    assert cfg.quant == "q8_0"


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in spec.metrics_for(SPEC["end_to_end"], cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(SPEC["per_layer"], cell)


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_is_wired(m):
    """Its cells exist and report the metric it moves, and a reader is
    found for it by name."""
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    target = {e["name"]: e for e in SPEC["end_to_end"]}[m["moves"]]
    for cell in m["workloads"]:
        assert cell in target.get("workloads", [cell])
    assert callable(spec.reader(m["name"]).read)
    assert m["layer"] and "\n" not in m["layer"]
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith((".py", ".json")) and "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_cell_mix_and_metric_are_found_from_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "chip_bench")
    bench = str(root / "chip_bench")
    (root / "chip_bench/traffic/burst.json").write_text(json.dumps(
        {"kind": "poisson", "rate_per_s": 7.0,
         "clip_seconds": {"dist": "uniform", "low": 1, "high": 2},
         "frames_per_second": 50,
         "out_tokens": {"dist": "uniform", "low": 3, "high": 5,
                        "integer": True}}))
    (root / "chip_bench/workloads/whisper-tiny-q8.burst.json").write_text(
        json.dumps({"limits": {"max_logit_gap": 1.0, "sample_tokens": 10,
                               "sample_requests": 4, "pad_tokens": 8},
                    "trace": {"seconds": 1.0}}))
    (root / "chip_bench/metrics/hello.burst.py").write_text(
        "def read(run):\n    return 1.5\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["workloads"].append({"name": "whisper-tiny-q8.burst",
                           "config": "whisper-tiny-q8", "traffic": "burst",
                           "chips": 1, "why": "a test cell"})
    s["per_layer"].append({"name": "hello.burst", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "scheduler", "moves": "latency_p95_ms",
                           "workloads": ["whisper-tiny-q8.burst"]})
    s["per_layer"].append({"name": "step_ms.burst", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "engine", "moves": "latency_p95_ms",
                           "workloads": ["whisper-tiny-q8.burst"]})
    cell = spec.resolve(s, "whisper-tiny-q8.burst", bench_dir=bench)
    assert cell["traffic"]["rate_per_s"] == 7.0
    assert [m["name"] for m in cell["per_layer"]] == ["hello.burst",
                                                      "step_ms.burst"]
    assert spec.reader("hello.burst", bench).read(None) == 1.5
    # a new cell's share of an existing quantity needs no file of its own
    assert spec.reader("step_ms.burst", bench).__file__.endswith(
        os.path.join("metrics", "step_ms.py"))
    after = _digest(root / "chip_bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_same_seed_same_traffic_other_seed_same_work():
    mix = json.load(open(os.path.join(BENCH, "traffic", "commands.json")))
    mix["rate_per_s"] = 40.0
    a = traffic.Traffic(dict(mix), 2**31 + 9, 5.0, 8, MEL80)
    b = traffic.Traffic(dict(mix), 2**31 + 9, 5.0, 8, MEL80)
    c = traffic.Traffic(dict(mix), 17, 5.0, 8, MEL80)
    key = lambda t: [(r.due, r.size, r.max_new) for r in t.requests]
    assert key(a) == key(b) and key(a) != key(c)
    assert sorted(r.max_new for r in a.requests) == \
        sorted(r.max_new for r in c.requests)
    assert sorted(r.size for r in a.requests) == \
        sorted(r.size for r in c.requests)
    assert len(a.requests) == 200
    a.prepare()
    b.prepare()
    assert all((x.payload == y.payload).all()
               for x, y in zip(a.requests, b.requests))
    assert not (a.requests[0].payload[:10] ==
                a.requests[1].payload[:10]).all()
    assert all(1 <= r.size / 50 <= 5 for r in a.requests)
    assert all(r.payload.shape == (r.size, 80) for r in a.requests)
    assert a.size_values() == sorted({r.size for r in a.requests})

    lf = json.load(open(os.path.join(BENCH, "traffic", "longform.json")))
    d = traffic.Traffic(lf, 5, 5.0, 4, MEL80)
    e = traffic.Traffic(lf, 5, 5.0, 4, MEL80)
    d.prepare(300)
    e.prepare(300)
    assert [r.max_new for r in d.requests] == [r.max_new for r in e.requests]
    outs = [r.max_new for r in d.requests[:256]]
    assert min(outs) >= 20 and max(outs) <= 224
    assert 95 <= sorted(outs)[128] <= 105          # median about 100


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_open_loop_arrivals_are_poisson():
    """Counts in one-second bins spread as a Poisson count does (variance
    over mean near 1), gaps are exponential (coefficient of variation near
    1), and a block of 8 gaps varies as 8 independent gaps do."""
    mix = json.load(open(os.path.join(BENCH, "traffic", "commands.json")))
    mix["rate_per_s"] = 16.0
    t = traffic.Traffic(mix, 2**31 + 3, 200.0, 8, MEL80)
    due = np.array([r.due for r in t.requests])
    assert len(due) == 3200 and (np.diff(due) >= 0).all()
    assert 0.0 <= due[0] and due[-1] < 200.0
    counts = np.bincount(due.astype(int), minlength=200)
    assert 0.7 < counts.var() / counts.mean() < 1.3
    gaps = np.diff(due)
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
    blocks = gaps[:len(gaps) // 8 * 8].reshape(-1, 8).sum(1)
    assert 0.75 < blocks.var() / (8 * gaps.var()) < 1.25


@pytest.mark.parametrize("cell", CELLS)
def test_resolve_finds_the_family(cell):
    cell = spec.resolve(SPEC, cell)
    assert cell["family"] == "whisper"
    fam = spec.load_family(cell["family"])
    assert fam is spec.load_family("whisper")       # loaded once
    assert callable(fam.reference_logits)


def test_unknown_family_raises(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata",
                                                  "tests"))
    conf = json.loads((root / "chip_bench/configs/whisper-tiny-q8.json")
                      .read_text())
    (root / "chip_bench/configs/other.json").write_text(
        json.dumps(dict(conf, name="other", family="wav2vec")))
    shutil.copy(root / "chip_bench/workloads/whisper-tiny-q8.longform.json",
                root / "chip_bench/workloads/other.longform.json")
    s = json.loads(json.dumps(SPEC))
    s["configs"].append(dict(s["configs"][0], name="other",
                             file="chip_bench/configs/other.json"))
    s["workloads"].append(dict(s["workloads"][0], name="other.longform",
                               config="other"))
    with pytest.raises(KeyError, match=r"wav2vec.*\['decoder_lm', 'whisper'\]"):
        spec.resolve(s, "other.longform", bench_dir=str(root / "chip_bench"))
