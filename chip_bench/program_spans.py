"""The program's own spans on a profiler trace, beside the device's idle
time: what ``trace_reduce`` cannot see.

The serving program names its host work through ``repro.obs``: every span
is also a ``jax.profiler.TraceAnnotation`` named ``repro.<span>``
(``repro.admit``, ``repro.upload``, ``repro.prefill``, ``repro.splice``,
``repro.decode_step`` and its ``repro.step.*`` phases), on the
``/host:CPU`` plane and on the device trace's clock. This module keeps
those events and reduces them over the stretch ``trace_reduce`` uses,
cutting idle time exactly as it does: the complement, inside the stretch,
of the union of the first device's ``XLA Ops`` intervals.

``trace_reduce`` itself is left as it is, so every value it returns is
unchanged; ``load`` here returns the raw dict it reduces with a
``program`` list added, and ``reduce`` returns only the new keys.
"""
from __future__ import annotations

import functools
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chip_bench import trace_reduce

PREFIX = "repro."

Interval = Tuple[float, float]


def load(path: str) -> dict:
    """``trace_reduce.load``'s raw events, plus the host's ``repro.``
    events as ``program`` (start, end, name, request id or None; ns)."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> dict:
    """``load`` from a parsed ``ProfileData``: the same devices and
    ``bench.`` host spans ``trace_reduce.load`` keeps, in one pass."""
    devices, host, program = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in ln.events]
                     for ln in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
                    elif e.name.startswith(PREFIX):
                        program.append((e.start_ns,
                                        e.start_ns + e.duration_ns, e.name,
                                        dict(e.stats).get("rid")))
    return {"devices": devices, "host": sorted(host),
            "program": sorted(program)}


def idle_intervals(raw: dict) -> Optional[Tuple[Interval, List[Interval]]]:
    """The stretch and, inside it, the first device's idle intervals."""
    spans = [h for h in raw["host"] if h[2] == trace_reduce.STRETCH]
    if not spans or not raw["devices"]:
        return None
    lo, hi = spans[0][0], spans[0][1]
    dev = raw["devices"][min(raw["devices"])]
    iv = trace_reduce._clip(trace_reduce._union(
        [(a, b) for a, b, _ in dev["ops"]]), lo, hi)
    edges = [lo] + [x for ab in iv for x in ab] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return (lo, hi), idle


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in(idle: List[Interval], events: Iterable[tuple],
            lo: float, hi: float) -> Dict[str, float]:
    """Seconds of ``idle`` inside the union of each name's intervals."""
    by_name: Dict[str, List[Interval]] = defaultdict(list)
    for e in events:
        by_name[e[2]].append((e[0], e[1]))
    return {name: overlap(idle, trace_reduce._clip(
        trace_reduce._union(iv), lo, hi)) * 1e-9
        for name, iv in sorted(by_name.items())}


def reduce(raw: dict) -> Optional[dict]:
    """Per ``repro.`` span name inside the stretch, its count and host
    seconds (``program_spans``) and the seconds of device idle inside the
    union of its intervals (``idle_in_program_span``); the same idle for
    the harness's ``bench.`` spans (``idle_in_bench_span``), which the
    program's spans should account for. None where the trace holds no
    stretch or no device."""
    cut = idle_intervals(raw)
    if cut is None:
        return None
    (lo, hi), idle = cut
    prog = [e for e in raw.get("program", []) if e[1] > lo and e[0] < hi]
    spans: Dict[str, dict] = {}
    for a, b, name, _ in prog:
        s = spans.setdefault(name, {"count": 0, "seconds": 0.0})
        s["count"] += 1
        s["seconds"] += (min(b, hi) - max(a, lo)) * 1e-9
    bench = [h for h in raw["host"] if h[2] != trace_reduce.STRETCH
             and h[1] > lo and h[0] < hi]
    return {"program_spans": spans,
            "idle_in_program_span": idle_in(idle, prog, lo, hi),
            "idle_in_bench_span": idle_in(idle, bench, lo, hi)}


#: parent span -> its child spans, as the program nests them
CHILDREN = {
    "repro.admit": ("repro.upload", "repro.prefill", "repro.splice"),
    "repro.decode_step": ("repro.step.kv_usage", "repro.step.dispatch",
                          "repro.step.sync", "repro.step.ledger",
                          "repro.step.emit"),
}


def idle_split(reduced: dict, per: Dict[str, str]) -> Dict[str, dict]:
    """For each parent span, its idle and each child's, in ms per count
    of the denominator span ``per[parent]`` (``repro.decode_step`` or
    ``repro.prefill``), and the share of the parent's idle the children
    hold."""
    idle = reduced["idle_in_program_span"]
    counts = {k: v["count"] for k, v in reduced["program_spans"].items()}
    out = {}
    for parent, kids in CHILDREN.items():
        n = counts.get(per.get(parent, parent), 0)
        if parent not in idle or not n:
            continue
        tot = idle[parent]
        held = sum(idle.get(k, 0.0) for k in kids)
        out[parent] = {
            "ms_per": 1e3 * tot / n, "per": per.get(parent, parent),
            "children_ms_per": {k: 1e3 * idle.get(k, 0.0) / n
                                for k in kids},
            "children_share": held / tot if tot > 0 else None}
    return out


# -- device time per named scope --------------------------------------------
# The ``XLA Ops`` events carry the ``jax.named_scope`` path of their HLO
# operation as the ``tf_op`` stat of their event metadata (``jit(step_fn)/
# decoder/while/body/closed_call/self_attn/...``). ``ProfileData`` does not
# expose metadata stats, so ``scopes_s`` reads the file with a protobuf
# class declared here from the field numbers of the profiler's
# ``xplane.proto`` (only the fields read below).

@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chip_bench_xplane.proto", package="chip_bench_xplane")
    msgs = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64, None),
                  ("uint64_value", 3, F.TYPE_UINT64, None),
                  ("str_value", 5, F.TYPE_STRING, None),
                  ("ref_value", 7, F.TYPE_UINT64, None)],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64, None),
                   ("offset_ps", 2, F.TYPE_INT64, None),
                   ("duration_ps", 3, F.TYPE_INT64, None)],
        "XLine": [("name", 2, F.TYPE_STRING, None),
                  ("timestamp_ns", 3, F.TYPE_INT64, None),
                  ("events", 4, F.TYPE_MESSAGE, "XEvent")],
        "XEventMetadata": [("id", 1, F.TYPE_INT64, None),
                           ("name", 2, F.TYPE_STRING, None),
                           ("stats", 5, F.TYPE_MESSAGE, "XStat")],
        "XStatMetadata": [("id", 1, F.TYPE_INT64, None),
                          ("name", 2, F.TYPE_STRING, None)],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                               ("value", 2, F.TYPE_MESSAGE,
                                "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                              ("value", 2, F.TYPE_MESSAGE, "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_STRING, None),
                   ("lines", 3, F.TYPE_MESSAGE, "XLine"),
                   ("event_metadata", 4, F.TYPE_MESSAGE,
                    "EventMetadataEntry"),
                   ("stat_metadata", 5, F.TYPE_MESSAGE,
                    "StatMetadataEntry")],
        "XSpace": [("planes", 1, F.TYPE_MESSAGE, "XPlane")],
    }
    repeated = {"events", "stats", "lines", "event_metadata",
                "stat_metadata", "planes"}
    for name, fields in msgs.items():
        m = fd.message_type.add(name=name)
        for fname, num, typ, ref in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=(F.LABEL_REPEATED if fname in repeated
                                   else F.LABEL_OPTIONAL))
            if ref:
                f.type_name = f".chip_bench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chip_bench_xplane.XSpace"))


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The model scope an op_name path lies in: the first of ``scopes``
    (a family module's ``SCOPES``) whose parts appear in order among the
    path's parts."""
    parts = path.split("/")
    for scope in scopes:
        want = scope.split("/")
        i = 0
        for p in parts:
            if i < len(want) and p == want[i]:
                i += 1
        if i == len(want):
            return scope
    return None


def scopes_s(path: str, lo: float, hi: float,
             scopes: Sequence[str]) -> Dict[str, float]:
    """Device seconds per model scope of ``scopes``, over the first
    device's leaf ``XLA Ops`` inside [lo, hi] (ns); ops in no scope go
    under ``other``.
    Empty where the trace's ops carry no ``tf_op`` path."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devs = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    if not devs:
        return {}
    plane = min(devs, key=lambda p: p.name)
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    op_scope = {}
    for e in plane.event_metadata:
        tf_op = None
        for s in e.value.stats:
            if stat_names.get(s.metadata_id) == "tf_op":
                tf_op = s.str_value or stat_names.get(s.ref_value)
        op_scope[e.key] = (e.value.name,
                           scope_of(tf_op, scopes) if tf_op else None,
                           tf_op)
    if not any(v[2] for v in op_scope.values()):
        return {}
    out: Dict[str, float] = defaultdict(float)
    for ln in plane.lines:
        if ln.name != "XLA Ops":
            continue
        ops = []
        for ev in ln.events:
            a = ln.timestamp_ns + ev.offset_ps / 1e3
            b = a + ev.duration_ps / 1e3
            if a >= lo and b <= hi:
                ops.append((a, b, ev.metadata_id))
        for a, b, mid in trace_reduce._leaf_ops(ops):
            out[op_scope.get(mid, ("", None))[1] or "other"] += (b - a) * 1e-9
    return dict(out)
