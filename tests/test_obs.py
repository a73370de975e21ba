"""Observability subsystem (DESIGN.md §16): tracer span/phase semantics
under arbitrary lifecycle interleavings, the exact ledger-delta
attribution invariant (§16.2), histogram/percentile soundness, the
structural no-allocation guarantee of disabled telemetry, and the
Perfetto/Prometheus export contract (validated with the same
tools/check_trace.py CI runs)."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler

N_FRAMES = 8


def _load_check_trace():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "tools", "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def whisper_setup():
    cfg = get_smoke_config("whisper-tiny")
    params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
    return cfg, params


def _mels(cfg, n, rng=None):
    rng = rng or np.random.default_rng(0)
    return [rng.standard_normal((1, N_FRAMES, cfg.n_mels)).astype(np.float32)
            for _ in range(n)]


class _VClock:
    """Deterministic strictly-increasing clock for tracer tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-6
        return self.t


# ---------------------------------------------------------------------------
# Tracer: stack spans + lifecycle phases
# ---------------------------------------------------------------------------
def test_stack_spans_nest_and_close():
    tr = obs.Tracer(clock=_VClock())
    with tr.span("outer", cat="host"):
        with tr.span("inner", cat="host", args={"k": 1}):
            pass
    assert tr.all_closed()
    assert tr.check_nesting() == []
    # journal order is close order: inner closes first
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    inner, outer = tr.spans
    assert inner.args == {"k": 1}
    assert outer.ts_us <= inner.ts_us
    assert outer.ts_us + outer.dur_us >= inner.ts_us + inner.dur_us


def test_span_closes_on_exception():
    tr = obs.Tracer(clock=_VClock())
    with pytest.raises(ValueError):
        with tr.span("doomed"):
            raise ValueError("boom")
    assert tr.all_closed()
    assert [s.name for s in tr.spans] == ["doomed"]


def test_phase_lifecycle_and_rid_closure():
    tr = obs.Tracer(clock=_VClock())
    tr.begin(0, "queued")
    tr.begin(0, "decode")
    tr.end(0, "queued")
    assert 0 not in tr.rids_closed          # decode still open
    tr.end(0, "decode", steps=4)
    assert tr.rids_closed == {0} == tr.rids_opened
    assert tr.all_closed()
    decode = [s for s in tr.spans if s.name == "decode"][0]
    assert decode.args["steps"] == 4
    assert decode.track == obs.request_track(0)


def test_phase_double_begin_and_unopened_end_raise():
    tr = obs.Tracer(clock=_VClock())
    tr.begin(1, "queued")
    with pytest.raises(RuntimeError):
        tr.begin(1, "queued")
    with pytest.raises(RuntimeError):
        tr.end(1, "decode")
    assert tr.open_phases() == [(1, "queued")]
    assert not tr.all_closed()


def test_instant_events_pick_request_track():
    tr = obs.Tracer(clock=_VClock())
    tr.instant("submit", rid=3)
    tr.instant("plan_build")
    a, b = tr.events
    assert (a.track, b.track) == (obs.request_track(3), obs.ENGINE_TRACK)
    assert a.instant and b.instant


# Legal per-rid lifecycle transitions, mirroring the schedulers: queued
# -> admit (decode opens) -> finish, or preempt (back to queued) and
# around again. The property: ANY interleaving of these ops across rids
# leaves a tracer whose phases all close and whose spans nest.
_ADMIT, _PREEMPT, _FINISH = 0, 1, 2


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 2)),
                max_size=60))
def test_phase_closure_under_any_interleaving(ops):
    tr = obs.Tracer(clock=_VClock())
    state = {}                              # rid -> "queued" | "decode"
    for rid, op in ops:
        if rid not in state:
            tr.instant("submit", rid=rid)
            tr.begin(rid, "queued")
            state[rid] = "queued"
        if op == _ADMIT and state[rid] == "queued":
            tr.end(rid, "queued")
            tr.begin(rid, "decode")
            state[rid] = "decode"
        elif op == _PREEMPT and state[rid] == "decode":
            tr.instant("preempt", rid=rid)
            tr.end(rid, "decode")
            tr.begin(rid, "queued")
            state[rid] = "queued"
        elif op == _FINISH and state[rid] == "decode":
            tr.end(rid, "decode")
            del state[rid]
    # drain the stragglers the way the scheduler drains its queue
    for rid, phase in sorted(state.items()):
        if phase == "queued":
            tr.end(rid, "queued")
            tr.begin(rid, "decode")
        tr.end(rid, "decode")
    assert tr.all_closed()
    assert tr.rids_closed == tr.rids_opened
    assert tr.check_nesting() == []
    # the export of a fully-closed tracer has no dangling "B" events
    evs = obs.export.trace_events(tr)["traceEvents"]
    assert not [e for e in evs if e["ph"] == "B"]


# ---------------------------------------------------------------------------
# Metrics: histogram + percentile
# ---------------------------------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(min_value=0, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                max_size=200))
def test_histogram_bucket_sum_invariant(values):
    h = obs.Histogram("h", buckets=obs.LATENCY_BUCKETS_S)
    for v in values:
        h.observe(v)
    assert sum(h.bucket_counts) == h.count == len(values)
    snap = h.snapshot()
    assert sum(c for _, c in snap["buckets"]) == snap["count"]


@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(min_value=-1e6, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=100),
       st.floats(min_value=0, max_value=100))
def test_percentile_matches_numpy(values, q):
    assert obs.percentile(values, q) == \
        pytest.approx(float(np.percentile(values, q)), rel=1e-9, abs=1e-9)


def test_histogram_bucket_sum_deterministic():
    h = obs.Histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0, 1e9):   # incl. two +Inf-bucket hits
        h.observe(v)
    assert sum(h.bucket_counts) == h.count == 5
    assert h.bucket_counts == [1, 1, 1, 2]


def test_tracked_histogram_percentiles_exact():
    h = obs.Histogram("h", track_values=True)
    xs = [0.001 * (i + 1) for i in range(20)]
    for v in xs:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(float(np.percentile(xs, q)))


def test_prometheus_exposition_cumulative_buckets():
    r = obs.MetricsRegistry()
    h = r.histogram("repro_t_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    r.counter("repro_n_total").inc(2, kind="a")
    text = r.render_prometheus()
    lines = text.splitlines()
    bucket_lines = [l for l in lines if l.startswith("repro_t_seconds_bucket")]
    cums = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
    assert cums == sorted(cums) and cums[-1] == 3   # cumulative, ends at count
    assert 'le="+Inf"' in bucket_lines[-1]
    assert 'repro_n_total{kind="a"} 2' in lines


# ---------------------------------------------------------------------------
# Telemetry: ledger spans (§16.2)
# ---------------------------------------------------------------------------
def test_ledger_spans_do_not_nest():
    tele = obs.Telemetry(clock=_VClock())
    with pytest.raises(RuntimeError):
        with tele.span("a", ledger=True):
            with tele.span("b", ledger=True):
                pass
    tele2 = obs.Telemetry(clock=_VClock())
    h = tele2.ledger_open("a")
    with pytest.raises(RuntimeError):
        tele2.ledger_open("b")
    tele2.ledger_close(h)
    with tele2.span("c", ledger=True):      # guard released after close
        pass


def test_ledger_open_close_matches_with_form():
    """The hot-path pair and the with-form record the same span shape and
    claim the same delta (here: zero, no ledger bound)."""
    tele = obs.Telemetry(clock=_VClock())
    with tele.span("step", cat="step", ledger=True, args={"active": 2}):
        pass
    h = tele.ledger_open("step")
    tele.ledger_close(h, cat="step", args={"active": 2})
    a, b = tele.tracer.spans
    assert a.name == b.name == "step"
    assert a.args == b.args == {"active": 2, "flops": 0, "calls": 0}
    assert tele.ledger_consistent()["exact"]


# ---------------------------------------------------------------------------
# Disabled telemetry allocates nothing (structural)
# ---------------------------------------------------------------------------
def test_disabled_telemetry_allocates_no_obs_objects(whisper_setup,
                                                     monkeypatch):
    """telemetry=None serving must never construct a Telemetry, Tracer,
    or Span — every instrumentation site is one ``is not None`` test.
    Proven structurally: constructors are patched to raise, then a full
    drain runs."""
    cfg, params = whisper_setup

    def _bomb(*a, **k):
        raise AssertionError("obs object constructed on the disabled path")

    import repro.obs.trace as trace_mod
    monkeypatch.setattr(obs.Telemetry, "__init__", _bomb)
    monkeypatch.setattr(trace_mod.Tracer, "__init__", _bomb)
    monkeypatch.setattr(trace_mod.Span, "__init__", _bomb)
    eng = ServeEngine(cfg, params, max_len=16, quant="none", eos_id=-1)
    sched = ContinuousBatchingScheduler(eng, n_slots=2, n_frames=N_FRAMES)
    for m in _mels(cfg, 3):
        sched.submit(m, max_new=3)
    res = sched.run()
    assert len(res) == 3
    assert all(len(r.tokens) == 3 for r in res.values())


# ---------------------------------------------------------------------------
# End-to-end: instrumented drains hold the §16.2 invariants
# ---------------------------------------------------------------------------
def test_continuous_drain_exact_attribution(whisper_setup, tmp_path):
    cfg, params = whisper_setup
    tele = obs.Telemetry()
    eng = ServeEngine(cfg, params, max_len=16, quant="q8_0",
                      offload=OffloadEngine(interpret=True,
                                            prefer_pallas=False),
                      eos_id=-1, telemetry=tele)
    sched = ContinuousBatchingScheduler(eng, n_slots=2, n_frames=N_FRAMES)
    rids = [sched.submit(m, max_new=4) for m in _mels(cfg, 4)]
    res = sched.run()
    assert set(res) == set(rids)

    cons = tele.ledger_consistent()
    assert cons["exact"], cons              # integer equality, not approx
    assert cons["claimed_flops"] > 0 and cons["claimed_calls"] > 0
    assert tele.tracer.all_closed()
    assert tele.tracer.check_nesting() == []
    assert tele.tracer.rids_closed == set(rids)

    # run() flushed the buffered step metrics into the registry
    m = tele.metrics
    assert m.counter("repro_tokens_total").value() == 16
    assert m.counter("repro_requests_submitted_total").value() == 4
    assert m.counter("repro_requests_finished_total").value() == 4
    assert m.histogram("repro_ttft_seconds").count == 4
    assert m.histogram("repro_step_seconds").count == \
        sum(1 for s in tele.tracer.spans if s.name == "decode_step")

    # exports: trace passes the CI validator, snapshot is JSON-safe
    trace_path = tmp_path / "t.json"
    tele.write_trace(str(trace_path))
    with open(trace_path) as f:
        assert _load_check_trace().validate(json.load(f)) == []
    json.dumps(tele.snapshot(), default=str)
    text = tele.write_metrics(str(tmp_path / "m.prom"))
    assert os.path.exists(text)


def test_paged_drain_with_preemption_and_sharing(whisper_setup):
    """The §16.2 invariants survive the paged scheduler's hard paths:
    prefix-shared admissions, CoW splits, preempt-and-replay."""
    cfg, params = whisper_setup
    tele = obs.Telemetry()
    eng = ServeEngine(cfg, params, max_len=32, quant="q8_0",
                      offload=OffloadEngine(interpret=True,
                                            prefer_pallas=False),
                      eos_id=-1, telemetry=tele)
    shared = _mels(cfg, 1)[0]
    # starved self arena (test_paging.py geometry) -> preemptions
    sched = eng.paged_scheduler(n_slots=3, n_frames=N_FRAMES, page_size=4,
                                n_pages=5)
    rids = [sched.submit(shared, max_new=6) for _ in range(3)]
    res = sched.run()
    assert set(res) == set(rids)
    assert sched.preemptions > 0

    cons = tele.ledger_consistent()
    assert cons["exact"], cons
    assert tele.tracer.all_closed()
    assert tele.tracer.check_nesting() == []
    names = {e.name for e in tele.tracer.events}
    assert "preempt" in names and "replay" in names
    assert "prefix_hit" in names            # identical mels share pages
    m = tele.metrics
    assert m.counter("repro_preemptions_total").value() == sched.preemptions
    assert m.counter("repro_replays_total").value() > 0
    # replay re-decode is claimed by the replay ledger span, so the
    # per-request "decode" phases may open/close more than once per rid
    assert tele.tracer.rids_closed == set(rids)


def test_attribution_reports_lifecycle_timings(whisper_setup):
    cfg, params = whisper_setup
    eng = ServeEngine(cfg, params, max_len=16, quant="none", eos_id=-1)
    sched = ContinuousBatchingScheduler(eng, n_slots=2, n_frames=N_FRAMES)
    rids = [sched.submit(m, max_new=3) for m in _mels(cfg, 3)]
    while sched.n_queued or sched.n_active:
        sched.admit()
        sched.decode_step()
    att = sched.attribution()
    assert set(att["per_request_queue_wait_s"]) == set(rids)
    assert set(att["per_request_ttft_s"]) == set(rids)
    assert all(v >= 0 for v in att["per_request_queue_wait_s"].values())
    # TTFT includes queue wait + prefill, so it dominates the wait
    assert all(att["per_request_ttft_s"][r] >=
               att["per_request_queue_wait_s"][r] for r in rids)
