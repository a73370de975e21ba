"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time and a breakdown, over the stretch the harness marked.

What the trace holds on a TPU (one ``/device:TPU:<n>`` plane per chip):
an ``XLA Modules`` line with one event per executed program
(``jit_prefill_fn(...)``, ``jit_step_fn(...)``) and an ``XLA Ops`` line
with one event per executed HLO operation, named by its HLO text
(``%q8_matmul.36 = f32[1504,384]... custom-call(...)``). Control-flow
operations (the layer scan's ``while``) enclose the operations they run,
so busy time is the union of the intervals, not their sum. The harness's
own host spans (``jax.profiler.TraceAnnotation``, names starting with
``bench.``) are on the ``/host:CPU`` plane, on the same clock.

The stretch is the span ``bench.stretch``. An idle gap is a stretch of
time inside it in which no operation runs on the device; it is labelled by
the innermost ``bench.`` span the host was in at the gap's midpoint.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

STRETCH = "bench.stretch"
#: kernel name -> how its custom calls are named in the HLO text: the
#: jitted wrapper's name, alone or under a vmap (``vmap_jit_q8_matmul_``)
KERNELS = {"q8_matmul": re.compile(r"^%(?:\w*_)?q8_matmul[_.\s]"),
           "q8_matvec": re.compile(r"^%(?:\w*_)?q8_matvec[_.\s]")}

Interval = Tuple[float, float]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _op_name(hlo: str) -> str:
    """``%copy.175 = bf16[...] copy(...)`` -> ``copy.175``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> dict:
    """The raw events the reduction needs: per device its ops and
    programs, and the host's ``bench.`` spans; times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in ln.events]
                     for ln in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in ln.events if e.name.startswith("bench.")]
    return {"devices": devices, "host": sorted(host)}


def _leaf_ops(ops):
    """Operations that enclose no other operation (drops the ``while``
    of a layer scan, keeps what it ran)."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    leaves = []
    for i, (a, b, n) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] >= a and nxt[1] <= b and nxt[0] < b:
            continue
        leaves.append((a, b, n))
    return leaves


def reduce(raw: dict, top: int = 10) -> Optional[dict]:
    """Busy and window seconds (busy averaged over the devices), each
    kernel's calls (HLO text and seconds) and time, the devices' top
    operations, the longest idle gaps by host span, and the seconds per
    program. None where the trace holds no stretch or no device."""
    spans = [h for h in raw["host"] if h[2] == STRETCH]
    if not spans or not raw["devices"]:
        return None
    lo, hi = spans[0][0], spans[0][1]
    window = (hi - lo) * 1e-9
    busy, gaps = [], []
    kernels: Dict[str, list] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    for name, dev in sorted(raw["devices"].items()):
        iv = _clip(_union([(a, b) for a, b, _ in dev["ops"]]), lo, hi)
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        if name == min(raw["devices"]):
            edges = [lo] + [x for ab in iv for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        for a, b, hlo in _leaf_ops([e for e in dev["ops"]
                                    if e[0] >= lo and e[1] <= hi]):
            op_time[_op_name(hlo)] += (b - a) * 1e-9
            for k, pat in KERNELS.items():
                if pat.match(hlo):
                    kernels[k].append((hlo, (b - a) * 1e-9))
        for a, b, n in dev["modules"]:
            if a >= lo and b <= hi:
                modules[n.split("(")[0]] += (b - a) * 1e-9
    inner = [h for h in raw["host"] if h[2] != STRETCH
             and h[1] > lo and h[0] < hi]

    def label(a, b):
        mid = (a + b) / 2
        over = [h for h in inner if h[0] <= mid < h[1]]
        return min(over, key=lambda h: h[1] - h[0])[2] if over else STRETCH

    gaps = sorted(((label(a, b), (b - a) * 1e-9) for a, b in gaps),
                  key=lambda g: -g[1])
    n_dev = len(raw["devices"])
    return {
        "window_s": window,
        "busy_s": sum(busy) / n_dev,
        "kernels": dict(kernels),
        "kernel_s": {k: sum(t for _, t in v) for k, v in kernels.items()},
        "modules_s": {k: v / n_dev for k, v in modules.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [list(g) for g in gaps[:top]],
        "idle_by_span": _by_span(gaps),
    }


def _by_span(gaps) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for name, s in gaps:
        out[name] += s
    return dict(out)
