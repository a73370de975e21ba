"""Everything about a cell is found by name.

``BENCHMARK.json`` (at the checkout's root) lists the configurations,
cells and metrics. For a cell ``<config>.<mix>`` the harness reads:

  configs/<config>.json     the model, its published sizes, the deployment
  traffic/<mix>.json        the mix's parameters, for ``traffic.Traffic``
  workloads/<cell>.json     the cell's overrides of the mix (a rate), its
                            correctness limits and its traced stretch
  metrics/<metric>.py       the reader of a per-layer metric; a metric
                            named <base>.<part> without a file of its own
                            is read by metrics/<base>.py (its layer and
                            the metric it moves are BENCHMARK.json's)

Adding a configuration, a mix, a cell or a metric is adding files and
entries; no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def resolve(spec: dict, workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """The merged description of one cell: its ``BENCHMARK.json`` entry,
    config, traffic parameters, limits and metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cell_file = _json(os.path.join(bench_dir, "workloads", f"{workload}.json"))
    traffic = _json(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    traffic.update(cell_file.get("traffic", {}))
    return {
        "name": workload, "chips": w["chips"],
        "config": _json(os.path.join(os.path.dirname(bench_dir),
                                     conf["file"])),
        "traffic": traffic,
        "limits": cell_file["limits"],
        "trace": cell_file["trace"],
        "end_to_end": metrics_for(spec["end_to_end"], workload),
        "per_layer": metrics_for(spec["per_layer"], workload),
        "bench_dir": bench_dir,
    }


def metrics_for(metrics: List[dict], workload: str) -> List[dict]:
    """The metrics a cell reports: those whose ``workloads`` list names
    it, and those without the list."""
    return [m for m in metrics if workload in m.get("workloads", [workload])]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the published sizes the file states (a ``smoke`` key, used
    only by the CPU tests, shrinks it with ``configs.base.reduced``)."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    cfg = get_config(conf["arch"])
    if "smoke" in conf:
        return reduced(cfg, **conf["smoke"])
    pub = conf["published"]
    got = {"d_model": cfg.d_model, "encoder_layers": cfg.num_encoder_layers,
           "decoder_layers": cfg.num_layers,
           "encoder_attention_heads": cfg.num_heads,
           "decoder_attention_heads": cfg.num_heads,
           "encoder_ffn_dim": cfg.d_ff, "decoder_ffn_dim": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "num_mel_bins": cfg.n_mels,
           "max_source_positions": cfg.encoder_ctx}
    bad = {k: (got[k], pub[k]) for k in got if got[k] != pub[k]}
    if bad:
        raise ValueError(f"{conf['arch']} differs from its published "
                         f"config (program, published): {bad}")
    return dataclasses.replace(cfg, quant=conf["quant"])


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The module of per-layer metric ``name``: ``metrics/<name>.py``, or
    else ``metrics/<base>.py`` for a name ``<base>.<part>``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(bench_dir, "metrics", f"{name.split('.')[0]}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "chip_bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
