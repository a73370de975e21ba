"""Operations, bytes and the metric arithmetic, against hand counts."""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

from chip_bench import harness, spec, stats, work
from chip_bench.peaks import peaks
from repro.configs.registry import get_config

TINY = get_config("whisper-tiny")

# the encoder FFN up-projection as the chip's trace names it: M 1500
# padded to 1504, the main K segment 256 of 384, N 1536
FFN_UP = ("%q8_matmul.40 = f32[1504,1536]{1,0:T(8,128)S(1)} custom-call("
          "bf16[1504,256]{1,0:T(8,128)(2,1)S(1)} %pad.35, "
          "s8[1536,256]{1,0:T(8,128)(4,1)S(1)} %copy.96, "
          "f32[1536,8]{1,0:T(8,128)S(1)} %bitcast.196), "
          "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
          "{bf16[1504,256]{1,0}, s8[1536,256]{1,0}, f32[1536,8]{1,0}}")


def test_ffn_up_q8_matmul_hand_count():
    ops = 2 * 1504 * 256 * 1536                       # 1,182,793,728
    nbytes = (1504 * 256 * 2        # x, bf16:          770,048
              + 1536 * 256          # int8 weights:     393,216
              + 1536 * 8 * 4        # f32 scales:        49,152
              + 1504 * 1536 * 4)    # f32 out:        9,240,576
    assert (ops, nbytes) == (1_182_793_728, 10_452_992)
    assert work.kernel_call(FFN_UP) == (ops, nbytes)
    assert work.q8_work(1504, 256, 1536) == (ops, nbytes)


def test_vocab_q8_matvec_hand_count():
    # dec.vocab on 8 decode rows: K main 256 of 384, N 51872
    assert work.q8_work(8, 256, 51872) == (
        2 * 8 * 256 * 51872,                          # 212,467,712
        8 * 256 * 2 + 51872 * 256 + 51872 * 8 * 4 + 8 * 51872 * 4)
    assert work.q8_work(8, 256, 51872)[1] == 16_603_136
    hlo = ("%q8_matvec.7 = f32[8,52224]{1,0} custom-call(bf16[8,256]{1,0} "
           "%a, s8[52224,256]{1,0} %b, f32[52224,8]{1,0} %c), "
           "custom_call_target=\"tpu_custom_call\"")
    assert work.kernel_call(hlo) == work.q8_work(8, 256, 52224)


def test_vmapped_cross_kv_call():
    hlo = ("%vmap_jit_q8_matmul__.4 = f32[4,1504,384]{2,1,0} custom-call("
           "bf16[1504,256]{1,0} %p, s8[4,384,256]{2,1,0} %q, "
           "f32[4,384,8]{2,1,0} %s), custom_call_target=\"tpu_custom_call\"")
    assert work.kernel_call(hlo) == work.q8_work(1504, 256, 384, batch=4)
    assert work.kernel_call("%fusion.3 = f32[8] fusion(f32[8] %x)") is None


def test_token_and_prefill_flops_hand_count():
    d, f, ff, v = 384, 1500, 1536, 51865
    layer = (2 * d * 3 * d + 2 * d * d        # self q, k, v, o
             + 4 * 10 * d                     # self attention over 10
             + 2 * 2 * d * d                  # cross q, o
             + 4 * f * d                      # cross attention over 1500
             + 4 * d * ff)                    # MLP
    assert layer == 6_448_128
    assert work.token_flops(TINY, 10, f) == 4 * layer + 2 * d * v
    assert work.token_flops(TINY, 10, f) == 65_624_832
    enc = 2 * f * d * 4 * d + 4 * f * f * d + 4 * f * d * ff
    assert work.prefill_flops(TINY, f) == (2 * f * 80 * d + 4 * enc
                                           + 4 * 2 * f * d * 2 * d)


def test_no_metric_reads_the_offload_ledger():
    """The ledger counts a scanned layer stack once per program; nothing
    the benchmark reports may come from it."""
    banned = re.compile(r"\.(ledger|stats|by_backend|by_kernel|by_device|"
                        r"offloaded_flops|fallback_flops|energy_report)\b")
    files = [os.path.join(spec.BENCH_DIR, f) for f in
             ("harness.py", "work.py", "trace_reduce.py", "stats.py")]
    files += [os.path.join(spec.BENCH_DIR, "metrics", f)
              for f in os.listdir(os.path.join(spec.BENCH_DIR, "metrics"))
              if f.endswith(".py")]
    for path in files:
        with open(path) as fh:
            for n, line in enumerate(fh, 1):
                code = line.split("#")[0]
                assert not banned.search(code), f"{path}:{n}: {line}"


def test_unknown_device_has_no_peaks():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")


def _run(requests, t0=100.0, t1=110.0):
    return harness.Run(cell={"chips": 1}, cfg=TINY, n_slots=4, frames=1500,
                       t0=t0, t1=t1, requests=requests, steps=[], admits=[],
                       peaks={})


def test_p95_counts_every_request_from_its_due_time():
    reqs = {}
    for i in range(40):
        due = 100.0 + 0.2 * i
        # submitted late by i ms: the wait from due must count
        reqs[i] = harness.Served(i, 2, np.zeros((1, 1)), submit=due + 1e-3 * i,
                                 due=due,
                                 token_t=[due + 0.01 * (i % 7) + 0.05,
                                          due + 0.5])
    # one due outside the window is not counted
    reqs[99] = harness.Served(99, 2, np.zeros((1, 1)), submit=120.0,
                              due=120.0, token_t=[150.0, 151.0])
    e = harness.end_to_end(_run(reqs), 10.0)
    ttft = [0.01 * (i % 7) + 0.05 for i in range(40)]
    assert e["ttft_p95_ms"] == pytest.approx(1e3 * stats.percentile(ttft, 95))
    assert e["latency_p95_ms"] == pytest.approx(500.0)
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95


def test_tokens_over_the_whole_window():
    reqs = {0: harness.Served(0, 5, np.zeros((1, 1)), submit=99.0,
                              token_t=[99.5, 101.0, 105.0, 109.9, 110.5]),
            1: harness.Served(1, 3, np.zeros((1, 1)), submit=100.0,
                              token_t=[100.2, 100.4, 100.6])}
    e = harness.end_to_end(_run(reqs), 10.0)
    assert e["tokens_per_s"] == pytest.approx(6 / 10.0)
    gaps = [1.5, 4.0, 4.9, 0.2, 0.2]            # later token in the window
    assert e["token_gap_p95_ms"] == pytest.approx(
        1e3 * stats.percentile(gaps, 95))
