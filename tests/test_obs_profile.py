"""The obs spans' profiler sink (DESIGN.md §16.1): under ``jax.profiler``
every span lands on the ``/host:CPU`` plane as ``repro.<name>``, nested as
the program nests it, with or without a ``Telemetry``; with one, the
journal holds the same names."""
import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler

N_FRAMES = 8
ADMIT = ("admit", "upload", "prefill", "splice")
STEP = ("decode_step", "step.kv_usage", "step.dispatch", "step.sync",
        "step.ledger", "step.emit")


@pytest.fixture(scope="module")
def whisper_setup():
    cfg = get_smoke_config("whisper-tiny")
    params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
    return cfg, params


def _drive(sched, cfg, n_req=3):
    rng = np.random.default_rng(0)
    rids = [sched.submit(rng.standard_normal((N_FRAMES, cfg.n_mels))
                         .astype(np.float32), max_new=3)
            for _ in range(n_req)]
    while sched.n_queued or sched.n_active:
        sched.admit()
        sched.decode_step()
    return rids


def _profiled(tmp_path, sched, cfg):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        rids = _drive(sched, cfg)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
          for p in pd.planes if p.name == "/host:CPU"
          for ln in p.lines for e in ln.events
          if e.name.startswith(obs.PREFIX)]
    return rids, ev


def _inside(child, parents):
    return [p for p in parents if p[0] <= child[0] and child[1] <= p[1]]


def _check_nested(ev, rids):
    by = {}
    for e in ev:
        by.setdefault(e[2][len(obs.PREFIX):], []).append(e)
    assert set(ADMIT + STEP) <= set(by)
    for name, parent in [(n, "admit") for n in ADMIT[1:]] + \
            [(n, "decode_step") for n in STEP[1:]]:
        for e in by[name]:
            assert len(_inside(e, by[parent])) == 1, (name, e)
    # the per-request spans carry the request id, one set per request
    for name in ADMIT[1:]:
        assert sorted(e[3]["rid"] for e in by[name]) == sorted(rids)
    # the children cover their parent's host time
    for parent, kids in (("admit", ADMIT[1:]), ("decode_step", STEP[1:])):
        for p in by[parent]:
            held = sum(e[1] - e[0] for k in kids for e in by[k]
                       if _inside(e, [p]))
            if held:                     # an admit pass may admit nothing
                assert held >= 0.9 * (p[1] - p[0]), (parent, held, p)
    return by


def test_disabled_telemetry_spans_reach_the_profiler(whisper_setup,
                                                     tmp_path):
    cfg, params = whisper_setup
    eng = ServeEngine(cfg, params, max_len=16, quant="none", eos_id=-1)
    sched = ContinuousBatchingScheduler(eng, n_slots=2, n_frames=N_FRAMES)
    _drive(sched, cfg, n_req=1)                    # compile outside
    rids, ev = _profiled(tmp_path, sched, cfg)
    by = _check_nested(ev, rids)
    assert len(by["decode_step"]) == len(by["step.emit"]) >= 3


def test_telemetry_journal_holds_the_profiler_names(whisper_setup, tmp_path):
    cfg, params = whisper_setup
    tele = obs.Telemetry()
    eng = ServeEngine(cfg, params, max_len=16, quant="none", eos_id=-1,
                      telemetry=tele)
    sched = ContinuousBatchingScheduler(eng, n_slots=2, n_frames=N_FRAMES)
    _drive(sched, cfg, n_req=1)                    # compile outside
    n0 = len(tele.tracer.spans)
    rids, ev = _profiled(tmp_path, sched, cfg)
    _check_nested(ev, rids)
    new = tele.tracer.spans[n0:]
    journal = sorted(s.name for s in new if s.cat in ("sched", "step")
                     or s.name in ADMIT)
    profiled = sorted(e[2][len(obs.PREFIX):] for e in ev)
    # one record per interval in each sink: the step's ledger span IS the
    # profiled ``repro.decode_step``, not a second span beside it
    assert journal == profiled
    assert set(ADMIT + STEP) <= set(journal)
    assert tele.tracer.check_nesting() == []
    assert tele.ledger_consistent()["exact"]


def test_phases_are_back_to_back_and_journalled():
    tr = obs.Tracer()
    with obs.Phases(tr, cat="step") as ph:
        ph("a")
        ph("b")
    a, b = tr.spans
    assert (a.name, b.name) == ("a", "b")
    assert a.ts_us + a.dur_us <= b.ts_us + 1e-6
    with obs.Phases(None) as ph:                  # annotations alone
        ph("c")
    assert len(tr.spans) == 2
