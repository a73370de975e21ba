"""Autotuning subsystem (DESIGN.md §9): candidate space budget enforcement,
deterministic winner selection, cache roundtrip/merge, and the
OffloadEngine cache-hit fast path."""
import jax
import numpy as np
import pytest

from repro.core.offload import OffloadEngine
from repro.core.mixed_exec import select_burst
from repro.core.qformats import QBLOCK, dequantize_q8_0, quantize_q8_0
from repro.tuning import (
    Autotuner, TuningCache, TuningKey, TuningRecord, analytic_cost,
    enumerate_candidates, kernel_for, padded_m)
from repro.tuning.space import VMEM_FULL_BYTES


# ---------------------------------------------------------------------------
# candidate space
# ---------------------------------------------------------------------------
def test_candidates_respect_vmem_budget():
    budget = 256 * 1024
    cands = enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                 vmem_budget_bytes=budget)
    assert cands
    assert all(c.vmem_bytes <= budget for c in cands)
    # every candidate tiles the problem exactly and honors the Q8_0 rule
    for c in cands:
        assert 1504 % c.block_m == 0
        assert 384 % c.block_n == 0
        assert 1536 % c.block_k == 0
        assert c.block_k % QBLOCK == 0


def test_budget_rejection_shrinks_space():
    big = enumerate_candidates("q8_matmul", 1504, 384, 1536,
                               vmem_budget_bytes=VMEM_FULL_BYTES)
    small = enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                 vmem_budget_bytes=64 * 1024)
    assert len(small) < len(big)
    oversized = [c for c in big if c.vmem_bytes > 64 * 1024]
    assert oversized                       # the big space has oversize tiles
    assert not [c for c in small if c.vmem_bytes > 64 * 1024]


def test_nothing_fits_tiny_budget():
    assert enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                vmem_budget_bytes=1024) == []


def test_matvec_space_streams_n_only():
    cands = enumerate_candidates("q8_matvec", 8, 1536, 384,
                                 vmem_budget_bytes=VMEM_FULL_BYTES)
    assert cands
    for c in cands:
        assert c.block_m == 8 and c.block_k == 384
        assert 1536 % c.block_n == 0


# ---------------------------------------------------------------------------
# deterministic winner under the analytic model
# ---------------------------------------------------------------------------
def test_winner_deterministic():
    a = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    b = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    ra = a.search("q8_matmul", 1504, 384, 1536)
    rb = b.search("q8_matmul", 1504, 384, 1536)
    assert ra == rb
    assert ra.source == "analytic"
    assert ra.vmem_bytes <= 2**21


def test_winner_beats_or_matches_every_candidate():
    tun = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    rec = tun.search("q8_matmul", 1504, 384, 1536)
    for c in enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                  vmem_budget_bytes=2**21):
        assert rec.cost_s <= analytic_cost(c, 1504, 384, 1536).cost_s


def test_search_none_when_nothing_admissible():
    tun = Autotuner(vmem_budget_bytes=1024, mode="analytic")
    assert tun.search("q8_matmul", 1504, 384, 1536) is None
    assert tun.best_tiling("q8_matmul", 1504, 384, 1536, "q8_0") is None


def test_negative_results_memoized():
    """Shapes with no admissible tiling must not re-sweep on the hot
    dispatch path: one search, then memoized misses."""
    tun = Autotuner(vmem_budget_bytes=1024, mode="analytic")
    for _ in range(4):
        assert tun.best_tiling("q8_matmul", 1504, 384, 1536, "q8_0") is None
    assert tun.searches == 1


def test_sweep_grid_budget_monotone_and_admissible():
    from repro.tuning import budget_grid, sweep_grid
    budgets = budget_grid(min_kb=64, agg_units=1)
    cells = sweep_grid("q8_matmul", 1504, 384, 1536, budgets=budgets,
                       block_ks=(128, 256, 512))
    assert cells
    for budget, rep in cells:
        assert rep.cand.vmem_bytes <= budget
    # at a fixed block_k, more budget never makes the best cell worse
    for bk in (128, 256, 512):
        costs = [r.cost_s for b, r in cells if r.cand.block_k == bk]
        assert all(b2 <= b1 + 1e-15 for b1, b2 in zip(costs, costs[1:]))


# ---------------------------------------------------------------------------
# cache: roundtrip, merge policy
# ---------------------------------------------------------------------------
def _key(k=1536, budget=2**21):
    return TuningKey("q8_matmul", 1504, 384, k, "q8_0", budget)


def test_cache_roundtrip(tmp_path):
    c = TuningCache()
    c.put(_key(), TuningRecord(94, 384, 512, 1e-4, 2**20, "analytic"))
    c.put(_key(768), TuningRecord(188, 128, 256, 2e-4, 2**19, "measured"))
    p = str(tmp_path / "cache.json")
    c.save(p)
    c2 = TuningCache.load(p)
    assert c2.entries == c.entries
    # key identity survives the string encoding
    k = _key()
    assert TuningKey.decode(k.encode()) == k


def test_cache_merge_prefers_measured_then_cheaper():
    a, b = TuningCache(), TuningCache()
    a.put(_key(), TuningRecord(94, 384, 512, 1e-4, 2**20, "analytic"))
    b.put(_key(), TuningRecord(32, 128, 256, 5e-4, 2**18, "measured"))
    a.merge(b)
    assert a.entries[_key()].source == "measured"   # measured wins
    c = TuningCache()
    c.put(_key(), TuningRecord(16, 128, 128, 9e-4, 2**17, "measured"))
    a.merge(c)
    assert a.entries[_key()].cost_s == 5e-4         # cheaper measured wins


def test_cache_schema_guard(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"schema": 999, "entries": {}}')
    with pytest.raises(ValueError):
        TuningCache.load(str(p))


def test_corrupt_cache_degrades_to_empty(tmp_path):
    """A cache is an optimization: a corrupt file must not fail engine
    construction — load_or_empty warns and starts empty."""
    p = tmp_path / "corrupt.json"
    p.write_text("garbage{{{")
    with pytest.warns(UserWarning, match="unreadable tuning cache"):
        tun = Autotuner(mode="analytic", cache_path=str(p))
    assert len(tun.cache) == 0


def test_autotuner_loads_cache_path(tmp_path):
    t1 = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    t1.best_tiling("q8_matmul", 1504, 384, 1536, "q8_0")
    p = str(tmp_path / "cache.json")
    t1.save(p)
    t2 = Autotuner(vmem_budget_bytes=2**21, mode="analytic", cache_path=p)
    rec = t2.best_tiling("q8_matmul", 1504, 384, 1536, "q8_0")
    assert t2.searches == 0                  # served from the loaded cache
    assert rec == t1.cache.entries[TuningKey("q8_matmul", 1504, 384, 1536,
                                             "q8_0", 2**21)]


# ---------------------------------------------------------------------------
# OffloadEngine integration: cache-hit fast path + numerical parity
# ---------------------------------------------------------------------------
def test_offload_engine_consumes_cached_tuning():
    tun = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    # pre-seed the cache with a distinctive winner for the full-K query the
    # engine makes; the engine must consume it without searching.
    key = TuningKey("q8_matvec", 8, 32, 64, "q8_0", 2**21)
    tun.cache.put(key, TuningRecord(8, 32, 32, 1e-6, 2**14, "measured"))
    eng = OffloadEngine(burst=256, prefer_pallas=True, interpret=True,
                        tuner=tun)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 64)) * 0.1
    y = eng.linear(x, quantize_q8_0(w), name="seeded")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w.T),
                               rtol=2e-2, atol=2e-2)
    assert eng.stats.tuned_calls == 1
    assert tun.searches == 0                # burst came from the cache...
    assert tun.cache.hits >= 1              # ...via the fast path
    # the seeded block_k=32 burst splits K=64 into main 64? no: 64//32*32=64,
    # so the whole K ran through the kernel with the cached tiling.


def test_offload_engine_fast_path_no_repeat_search():
    tun = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    eng = OffloadEngine(burst=32, prefer_pallas=True, interpret=True,
                        tuner=tun)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    wq = quantize_q8_0(jax.random.normal(jax.random.PRNGKey(1), (32, 64)) * 0.1)
    eng.linear(x, wq, name="a")
    n_first = tun.searches
    assert n_first >= 1
    for _ in range(3):
        eng.linear(x, wq, name="a")
    assert tun.searches == n_first          # later calls are dict lookups
    assert eng.stats.tuned_calls == 4


def test_tuned_parity_bf16_and_q8():
    tun = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    eng = OffloadEngine(burst=32, prefer_pallas=True, interpret=True,
                        tuner=tun)
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 96))
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 96)) * 0.1
    y = eng.linear(x, w, name="dense")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w.T),
                               rtol=2e-2, atol=2e-2)
    xq = jax.random.normal(jax.random.PRNGKey(4), (64, 128))
    wq = quantize_q8_0(
        jax.random.normal(jax.random.PRNGKey(5), (96, 128)) * 0.1)
    yq = eng.linear(xq, wq, name="quant")
    # held to its own math: Q8_0's quantisation error is the format's,
    # not the tiling's, so the reference uses the dequantized weight
    np.testing.assert_allclose(np.asarray(yq),
                               np.asarray(xq @ dequantize_q8_0(wq).T),
                               rtol=2e-2, atol=2e-2)


def test_select_burst_falls_back_without_tuner():
    assert select_burst(1536, None, default=256) == 256
    tun = Autotuner(vmem_budget_bytes=1024, mode="analytic")  # nothing fits
    assert select_burst(1536, tun, kernel="q8_matmul", m=1504, n=384,
                        dtype="q8_0", default=128) == 128


def test_kernel_for_matches_ops_dispatch():
    assert kernel_for(1, True) == "q8_matvec"       # decode batch
    assert kernel_for(16, True) == "q8_matvec"      # pads to 16
    assert kernel_for(17, True) == "q8_matmul"      # pads to 24 > 16
    assert kernel_for(1500, False) == "bf16_matmul"
    assert padded_m(1500) == 1504


def test_whisper_warm_tuning_populates_cache():
    from repro.configs.registry import get_config
    from repro.models.whisper import warm_tuning
    tun = Autotuner(vmem_budget_bytes=2**21, mode="analytic")
    eng = OffloadEngine(tuner=tun)
    cfg = get_config("whisper-tiny")
    n = warm_tuning(cfg, eng, n_frames=96, n_tokens=4)
    assert n > 0
    assert len(tun.cache) > 0
    assert warm_tuning(cfg, OffloadEngine()) == 0   # tunerless engine: no-op


@pytest.mark.parametrize("arch", ["whisper-tiny", "whisper-base",
                                  "whisper-small"])
def test_space_emits_only_chip_legal_tiles(arch):
    """Every tiling the space or the untuned default offers for a Whisper
    weight GEMM is one the TPU compiler accepts: lane dims (block_n,
    block_k) are multiples of 128 or the whole dim, the sublane dim
    (block_m) a multiple of 8 or the whole dim — N after lane padding,
    so the 51872-wide vocab readout gets 128-multiple tiles too."""
    from repro.configs.registry import get_config
    from repro.tuning import default_candidate
    from repro.tuning.space import LANE, SUBLANE, lane_padded
    cfg = get_config(arch)
    d, f = cfg.d_model, cfg.d_ff
    for n, k in ((3 * d, d), (d, d), (2 * d, d), (f, d), (d, f),
                 (cfg.padded_vocab, d)):
        for kernel, m in (("q8_matvec", 8), ("q8_matvec", 16),
                          ("q8_matmul", 1504), ("bf16_matmul", 1504)):
            cands = enumerate_candidates(kernel, m, n, k)
            assert cands
            for c in cands + [default_candidate(kernel, m, n, k)]:
                assert c.block_n % LANE == 0
                assert lane_padded(n) % c.block_n == 0
                assert c.block_k % LANE == 0 or c.block_k == k
                assert k % c.block_k == 0
                assert c.block_m % SUBLANE == 0 and m % c.block_m == 0
