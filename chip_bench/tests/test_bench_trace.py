"""``trace_reduce`` on a trace recorded on a TPU v5e by
``testdata/record_trace.py``: whisper-tiny at 2 slots, one admission,
two decode steps and a 3 ms host sleep, inside ``bench.stretch``. The
expected values were counted from the file's raw events: the stretch's
bounds, the union of the ``XLA Ops`` intervals in it, and the kernels'
custom calls (26 ``q8_matmul`` = 4 encoder layers x 6 projections + the
two vmapped cross-K/V projections; 66 ``q8_matvec`` = 2 steps x (4
layers x 8 projections + the vocabulary readout))."""
from __future__ import annotations

import lzma
import os

import pytest

from chip_bench import trace_reduce, work

TRACE = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata",
                     "tiny_2slots.xplane.pb.xz")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(lzma.decompress(open(TRACE, "rb").read()))
    return trace_reduce.reduce(trace_reduce.load(str(path)))


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.01948886, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.005331283, abs=1e-9)


def test_kernel_time_per_kernel(reduced):
    assert len(reduced["kernels"]["q8_matmul"]) == 26
    assert len(reduced["kernels"]["q8_matvec"]) == 66
    assert reduced["kernel_s"]["q8_matmul"] == pytest.approx(0.003036249,
                                                             abs=1e-9)
    assert reduced["kernel_s"]["q8_matvec"] == pytest.approx(0.000216141,
                                                             abs=1e-9)
    # every call's shapes are read: the encoder's 1504 x 256 x 384 calls
    ops = sorted(work.kernel_call(h)[0]
                 for h, _ in reduced["kernels"]["q8_matmul"])
    assert ops[0] == 2 * 1504 * 256 * 384


def test_idle_gaps_are_blamed_on_host_spans(reduced):
    name, secs = reduced["idle_gaps"][0]
    assert name == "bench.idle"
    assert secs == pytest.approx(0.005356833, abs=1e-9)
    assert [g[0] for g in reduced["idle_gaps"][1:4]] == [
        "bench.decode_step", "bench.admit", "bench.admit"]
    assert len(reduced["idle_gaps"]) <= 10
    total_idle = sum(reduced["idle_by_span"].values())
    assert total_idle == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-9)


def test_programs_and_top_ops(reduced):
    assert set(reduced["modules_s"]) >= {"jit_prefill_fn", "jit_step_fn"}
    assert len(reduced["device_ops"]) == 10
    top = reduced["device_ops"][0]
    assert top[1] >= reduced["device_ops"][-1][1]
    assert not any(n.startswith("while") for n, _ in reduced["device_ops"])
