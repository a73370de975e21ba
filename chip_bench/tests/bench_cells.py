"""A benchmark cell shrunk to run on a CPU in seconds: a configuration
and a mix, named ``<config>.<mix>`` as a cell is, with the model at the
repo's smoke widths (bf16, like the served configurations), the mix's
files from the benchmark's cell of that mix, and a short, light load."""
from __future__ import annotations

import json
import os

from chip_bench import spec

SMOKE = {"dtype": "bfloat16", "param_dtype": "bfloat16",
         "scan_layers": True, "encoder_ctx": 64, "vocab_size": 500,
         "vocab_pad": 12}
#: the logit-gap limit at these widths: sound runs read 0.002-0.006 and
#: the int4 control 0.14 and above (seeds 1-3)
SMOKE_GAP = 0.03


def smoke_cell(name: str) -> dict:
    conf_name, mix = name.split(".", 1)
    s = spec.load_spec()
    cell = spec.resolve(s, next(w["name"] for w in s["workloads"]
                                if w["traffic"] == mix))
    conf = {c["name"]: c for c in s["configs"]}[conf_name]
    with open(os.path.join(spec.ROOT, conf["file"])) as f:
        cell["config"] = dict(json.load(f), smoke=SMOKE,
                              deployment={"n_slots": 4, "max_len": 232})
    cell["name"] = name
    if cell["traffic"]["kind"] == "poisson":
        cell["traffic"].update(rate_per_s=8.0, frames_per_second=10)
    else:
        cell["traffic"].update(frames=64, expected_tokens_per_s=300)
    cell["limits"] = dict(cell["limits"], max_logit_gap=SMOKE_GAP)
    cell["trace"] = {"seconds": 0.3}
    return cell
