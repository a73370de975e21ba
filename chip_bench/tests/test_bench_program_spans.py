"""``program_spans`` on traces recorded on a TPU v5e by
``testdata/record_trace.py`` (whisper-tiny at 2 slots: one admission, two
decode steps, a 3 ms host sleep, inside ``bench.stretch``):
``tiny_2slots.xplane.pb.xz``, from a program with no ``repro.`` spans, and
``tiny_2slots_spans.xplane.pb.xz``, the same recording from a program
whose ``obs`` spans reach the profiler and whose model carries named
scopes."""
from __future__ import annotations

import lzma
import os

import pytest

from chip_bench import program_spans, spec, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")
CHILDREN = program_spans.CHILDREN


def _path(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(lzma.decompress(open(os.path.join(DATA, name),
                                          "rb").read()))
    return str(path)


@pytest.fixture(scope="module")
def old(tmp_path_factory):
    return _path(tmp_path_factory, "tiny_2slots.xplane.pb.xz")


@pytest.fixture(scope="module")
def new(tmp_path_factory):
    return _path(tmp_path_factory, "tiny_2slots_spans.xplane.pb.xz")


@pytest.mark.parametrize("trace", ["old", "new"])
def test_load_leaves_every_trace_reduce_value(trace, request):
    path = request.getfixturevalue(trace)
    ref = trace_reduce.load(path)
    raw = program_spans.load(path)
    assert (raw["devices"], raw["host"]) == (ref["devices"], ref["host"])
    assert trace_reduce.reduce(raw) == trace_reduce.reduce(ref)


def test_a_program_without_spans_reads_empty(old):
    raw = program_spans.load(old)
    red = program_spans.reduce(raw)
    assert raw["program"] == []
    assert red["program_spans"] == {}
    assert red["idle_in_program_span"] == {}
    assert program_spans.idle_split(red, {}) == {}
    # the harness's own spans still read: its 3 ms sleep is all idle
    t = trace_reduce.reduce(raw)
    bench = red["idle_in_bench_span"]
    assert bench["bench.idle"] == pytest.approx(0.0030953, abs=1e-6)
    assert sum(bench.values()) <= t["window_s"] - t["busy_s"] + 1e-9


def test_program_spans_nest_and_hold_the_idle(new):
    raw = program_spans.load(new)
    red = program_spans.reduce(raw)
    spans = red["program_spans"]
    assert spans["repro.admit"]["count"] == 1
    assert spans["repro.decode_step"]["count"] == 2
    for kids in CHILDREN.values():
        for k in kids:
            assert spans[k]["count"] >= 1, k
    idle = red["idle_in_program_span"]
    bench = red["idle_in_bench_span"]
    for parent, kids in CHILDREN.items():
        held = sum(idle[k] for k in kids)
        assert held <= idle[parent] + 1e-9
        assert held >= 0.9 * idle[parent], (parent, held, idle[parent])
    for name in ("decode_step", "admit"):
        assert idle["repro." + name] <= bench["bench." + name] + 1e-9
        assert idle["repro." + name] >= 0.9 * bench["bench." + name]
    rids = {e[3] for e in raw["program"] if e[2] == "repro.prefill"}
    assert rids == {e[3] for e in raw["program"] if e[2] == "repro.splice"}
    assert len(rids) == 1 and None not in rids


def test_spans_move_no_kernel_count(old, new):
    a = trace_reduce.reduce(trace_reduce.load(old))
    b = trace_reduce.reduce(trace_reduce.load(new))
    assert {k: len(v) for k, v in b["kernels"].items()} == \
        {k: len(v) for k, v in a["kernels"].items()} == \
        {"q8_matmul": 26, "q8_matvec": 66}


def test_scopes_split_the_device_time(old, new):
    for path, named in ((old, False), (new, True)):
        raw = program_spans.load(path)
        lo, hi = [(a, b) for a, b, n in raw["host"]
                  if n == trace_reduce.STRETCH][0]
        scopes = program_spans.scopes_s(path, lo, hi,
                                        spec.load_family("whisper").SCOPES)
        busy = trace_reduce.reduce(raw)["busy_s"]
        # leaf ops sum to the busy time, less the odd overlap
        assert sum(scopes.values()) == pytest.approx(busy, rel=0.02)
        assert (set(scopes) > {"other"}) == named
    for s in ("encoder/ffn", "cross_kv", "decoder/cross_attn", "readout"):
        assert scopes.get(s, 0.0) > 0.0, s


def test_idle_split_on_a_hand_made_reduction():
    red = {"program_spans": {"repro.decode_step": {"count": 4},
                             "repro.prefill": {"count": 2}},
           "idle_in_program_span": {
               "repro.decode_step": 0.010, "repro.step.emit": 0.006,
               "repro.step.dispatch": 0.003, "repro.admit": 0.004,
               "repro.splice": 0.003}}
    out = program_spans.idle_split(red, {"repro.admit": "repro.prefill"})
    step = out["repro.decode_step"]
    assert step["ms_per"] == pytest.approx(2.5)
    assert step["children_ms_per"]["repro.step.emit"] == pytest.approx(1.5)
    assert step["children_share"] == pytest.approx(0.9)
    adm = out["repro.admit"]
    assert (adm["per"], adm["ms_per"]) == ("repro.prefill", pytest.approx(2))
    assert adm["children_share"] == pytest.approx(0.75)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(5, 20)], 5),
    ([(0, 2), (4, 6), (8, 10)], [(1, 9)], 4),
    ([(0, 1)], [(1, 2)], 0),
    ([], [(0, 5)], 0),
])
def test_overlap_of_sorted_interval_lists(a, b, want):
    assert program_spans.overlap(a, b) == want
    assert program_spans.overlap(b, a) == want


@pytest.mark.parametrize("path,want", [
    ("jit(prefill_fn)/encoder/while/body/closed_call/checkpoint/self_attn/"
     "dot_general", "encoder/self_attn"),
    ("jit(prefill_fn)/encoder/conv/dot_general", "encoder/conv"),
    ("jit(prefill_fn)/cross_kv/vmap(jit(q8_matmul))/pallas_call",
     "cross_kv"),
    ("jit(step_fn)/decoder/while/body/closed_call/cross_attn/exp",
     "decoder/cross_attn"),
    ("jit(step_fn)/readout/dot_general", "readout"),
    ("jit(step_fn)/while/body/closed_call/reshape", None),
])
def test_scope_of_an_op_name_path(path, want):
    assert program_spans.scope_of(
        path, spec.load_family("whisper").SCOPES) == want
