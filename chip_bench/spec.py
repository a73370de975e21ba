"""Everything about a cell is found by name.

``BENCHMARK.json`` (at the checkout's root) lists the configurations,
cells and metrics. For a cell ``<config>.<mix>`` the harness reads:

  configs/<config>.json     the model, its family, its published sizes,
                            the deployment
  families/<family>.py      what the harness needs to know of a model
                            family: its published-sizes check, weights from
                            the seed, one request's payload, the scheduler's
                            and submit's arguments, the float32 reference
                            and the model FLOPs (``load_family``)
  traffic/<mix>.json        the mix's parameters, for ``traffic.Traffic``
  workloads/<cell>.json     the cell's overrides of the mix (a rate), its
                            correctness limits and its traced stretch
  metrics/<metric>.py       the reader of a per-layer metric; a metric
                            named <base>.<part> without a file of its own
                            is read by metrics/<base>.py (its layer and
                            the metric it moves are BENCHMARK.json's)

Adding a configuration, a mix, a cell or a metric is adding files and
entries; no file here names one. A configuration of a mechanism the
families here do not compute brings a ``families/<name>.py`` of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

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
    config = _json(os.path.join(os.path.dirname(bench_dir), conf["file"]))
    load_family(config["family"], bench_dir)
    return {
        "name": workload, "chips": w["chips"],
        "family": config["family"],
        "config": config,
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


def model_config(conf: dict, bench_dir: str = BENCH_DIR):
    """The program's ``ModelConfig`` for a configuration file: its
    family's check against the published sizes the file states, or, with
    a ``smoke`` key (used only by the CPU tests), the configuration shrunk
    by ``configs.base.reduced``."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    if "smoke" in conf:
        return reduced(get_config(conf["arch"]), **conf["smoke"])
    return load_family(conf["family"], bench_dir).model_config(conf)


def published(conf: dict, cfg, got: dict):
    """``cfg`` with the file's ``quant``, once every published size in
    ``got`` (published key -> the program's value) matches the file's
    ``published`` block; raises ``ValueError`` naming each that differs."""
    pub = conf["published"]
    bad = {k: (got[k], pub.get(k)) for k in got if got[k] != pub.get(k)}
    if bad:
        raise ValueError(f"{conf['arch']} differs from its published "
                         f"config (program, published): {bad}")
    return dataclasses.replace(cfg, quant=conf["quant"])


#: family modules by path: one module object per process, so the jitted
#: functions it holds compile once however often a cell asks for it
_FAMILIES: Dict[str, object] = {}


def load_family(name: str, bench_dir: str = BENCH_DIR):
    """The module ``families/<name>.py``, loaded once per process and
    path; a family module may load another's parts through this."""
    path = os.path.join(bench_dir, "families", f"{name}.py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.dirname(path))
                       if f.endswith(".py"))
        raise KeyError(f"no model family {name!r}; known: {known}")
    if path not in _FAMILIES:
        mod_spec = importlib.util.spec_from_file_location(
            "chip_bench_family_" + name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


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
