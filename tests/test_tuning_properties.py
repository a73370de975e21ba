"""Property tests for the tuning subsystem (DESIGN.md §9/§14): budget
admissibility of every enumerated tiling, the tuner picking inside its
own space, and cache/calibration stores round-tripping identity.
Hypothesis-backed via the _hyp shim — skip-marked on bare runtime
installs, exercised on the CI legs that install requirements-dev.txt."""
import json

from _hyp import given, settings, st

from repro.core.qformats import QBLOCK
from repro.tuning import (
    Autotuner, BackendCoefficients, CalibratedCoefficients, TuningCache,
    TuningKey, TuningRecord, enumerate_candidates)
from repro.tuning.space import LANE, SUBLANE, _claim_fn, lane_padded

# Dimension pools: mixes MXU-aligned sizes, Whisper's awkward 1504 =
# 2^5 x 47 padding, the tied vocab readout's 51872 (not a lane multiple),
# and sub-tile smalls — all within QBLOCK rules on K.
MS = (8, 24, 94, 128, 752, 1504)
NS = (128, 256, 384, 1152, 1536, 51872)
KS = (64, 384, 1536, 3072)
KERNS = ("q8_matmul", "q8_matvec", "bf16_matmul")
SRC = ("analytic", "calibrated", "measured")


@given(st.sampled_from(KERNS), st.sampled_from(MS), st.sampled_from(NS),
       st.sampled_from(KS), st.integers(2**13, 2**22))
@settings(max_examples=40, deadline=None)
def test_every_candidate_admissible(kernel, m, n, k, budget):
    """Every enumerated tiling divides its dims (N after lane padding),
    is a block the TPU compiler accepts, honors the Q8_0 block rule, and
    its recorded VMEM claim both fits the budget and equals the kernel's
    own vmem_claim_bytes recomputation."""
    claim = _claim_fn(kernel)
    for c in enumerate_candidates(kernel, m, n, k,
                                  vmem_budget_bytes=budget):
        assert m % c.block_m == 0
        assert lane_padded(n) % c.block_n == 0 and c.block_n % LANE == 0
        assert k % c.block_k == 0
        assert c.block_k % LANE == 0 or c.block_k == k
        assert c.block_m % SUBLANE == 0 or c.block_m == m
        if kernel.startswith("q8"):
            assert c.block_k % QBLOCK == 0
        assert c.vmem_bytes <= budget
        if kernel == "q8_matvec":
            assert c.vmem_bytes == claim(b=m, k=k, block_n=c.block_n)
        else:
            assert c.vmem_bytes == claim(block_m=c.block_m,
                                         block_n=c.block_n,
                                         block_k=c.block_k, k=k)


@given(st.sampled_from(KERNS), st.sampled_from(MS), st.sampled_from(NS),
       st.sampled_from(KS), st.integers(2**15, 2**22), st.booleans())
@settings(max_examples=25, deadline=None)
def test_tuner_pick_is_in_its_own_space(kernel, m, n, k, budget,
                                        calibrated):
    """search() returns an element of enumerate_candidates for the same
    arguments (or None exactly when that space is empty) — under both
    the analytic and a calibrated ranking."""
    cal = None
    if calibrated:
        cal = CalibratedCoefficients()
        cal.put(BackendCoefficients("xla_ref", 2e12, 3e10, 5e-7))
    tun = Autotuner(vmem_budget_bytes=budget, mode="analytic",
                    calibration=cal)
    rec = tun.search(kernel, m, n, k)
    space = enumerate_candidates(kernel, m, n, k, vmem_budget_bytes=budget)
    if rec is None:
        assert space == []
        return
    assert (rec.block_m, rec.block_n, rec.block_k) in {
        (c.block_m, c.block_n, c.block_k) for c in space}
    assert rec.source == ("calibrated" if calibrated else "analytic")


def _keys():
    return st.builds(TuningKey, st.sampled_from(KERNS),
                     st.sampled_from(MS), st.sampled_from(NS),
                     st.sampled_from(KS), st.sampled_from(("q8_0", "bf16")),
                     st.integers(2**13, 2**24))


def _records():
    pos = st.floats(min_value=1e-9, max_value=1e3, allow_nan=False,
                    allow_infinity=False)
    return st.builds(TuningRecord, st.sampled_from((8, 94, 128, 1504)),
                     st.sampled_from((128, 384, 512)),
                     st.sampled_from((32, 64, 256, 1536)), pos,
                     st.integers(2**10, 2**22), st.sampled_from(SRC))


@given(st.dictionaries(_keys(), _records(), max_size=6))
@settings(max_examples=25, deadline=None)
def test_cache_roundtrips_identity(entries):
    """to_dict -> json text -> from_dict is the identity on entries —
    including float costs bit-for-bit (the store must not drift tuner
    decisions between runs)."""
    c = TuningCache()
    for k, r in entries.items():
        c.put(k, r)
    back = TuningCache.from_dict(json.loads(json.dumps(c.to_dict())))
    assert back.entries == c.entries
    assert back.to_dict() == c.to_dict()


@given(st.lists(st.tuples(
    st.sampled_from(("pallas_tpu", "xla_ref", "host_residual")),
    st.floats(min_value=1e6, max_value=1e15, allow_nan=False),
    st.floats(min_value=1e6, max_value=1e13, allow_nan=False),
    st.floats(min_value=0, max_value=1e-3, allow_nan=False)),
    min_size=1, max_size=3, unique_by=lambda t: t[0]))
@settings(max_examples=25, deadline=None)
def test_calibration_store_roundtrips_identity(rows):
    cal = CalibratedCoefficients()
    for b, ef, bw, oh in rows:
        cal.put(BackendCoefficients(b, ef, bw, oh, n_samples=3))
    back = CalibratedCoefficients.from_dict(
        json.loads(json.dumps(cal.to_dict())))
    assert back.to_dict() == cal.to_dict()
    for b, ef, bw, oh in rows:
        got = back.for_backend(b)
        assert (got.eff_flops, got.eff_bw, got.overhead_s) == (ef, bw, oh)


# ---------------------------------------------------------------------------
# deterministic pins of the same properties (collectable without
# hypothesis, so the bare-runtime suite still covers one example each)
# ---------------------------------------------------------------------------
def test_admissibility_example():
    claim = _claim_fn("q8_matmul")
    for c in enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                  vmem_budget_bytes=2**20):
        assert c.vmem_bytes <= 2**20
        assert c.vmem_bytes == claim(block_m=c.block_m, block_n=c.block_n,
                                     block_k=c.block_k, k=1536)


def test_pick_in_space_example():
    tun = Autotuner(vmem_budget_bytes=2**20, mode="analytic")
    rec = tun.search("q8_matmul", 1504, 384, 1536)
    space = enumerate_candidates("q8_matmul", 1504, 384, 1536,
                                 vmem_budget_bytes=2**20)
    assert (rec.block_m, rec.block_n, rec.block_k) in {
        (c.block_m, c.block_n, c.block_k) for c in space}


def test_cache_roundtrip_example():
    c = TuningCache()
    c.put(TuningKey("q8_matmul", 1504, 384, 1536, "q8_0", 2**21),
          TuningRecord(94, 384, 512, 1.2345678901234e-4, 2**20,
                       "calibrated"))
    back = TuningCache.from_dict(json.loads(json.dumps(c.to_dict())))
    assert back.entries == c.entries
