"""The decode programs return only the state they write (DESIGN.md §11.2):
the step's output holds no cross-KV leaf, the pool keeps its cross-KV
arrays by reference across steps, the served tokens are those of the
one-shot path, and the step's written/kept bytes are gauged once."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.models import model as M
from repro.models import whisper as W
from repro.serve.engine import ServeEngine

# more frames than decode positions, as in served Whisper (1500 frames,
# a few hundred tokens): the read-only cross-KV outweighs the self-KV
N_FRAMES = 48
MAX_LEN = 16
POOLS = ("contiguous", "paged", "lm")


@pytest.fixture(scope="module")
def whisper_setup():
    cfg = get_smoke_config("whisper-tiny")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg, 64)


@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_smoke_config("qwen2.5-14b")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg, 64)


def _mels(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((1, N_FRAMES, cfg.n_mels)).astype(np.float32)
            for _ in range(n)]


def _scheduler(kind, whisper_setup, lm_setup, telemetry=None):
    cfg, params = lm_setup if kind == "lm" else whisper_setup
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, quant="none", eos_id=-1,
                      telemetry=telemetry)
    if kind == "paged":
        return eng.paged_scheduler(n_slots=2, n_frames=N_FRAMES, page_size=4)
    return eng.scheduler(n_slots=2, n_frames=None if kind == "lm"
                         else N_FRAMES)


def _read_only_leaves(state):
    """The leaves ``step_writes`` leaves out, taken from the state's type."""
    ls = state.layer_states
    if isinstance(ls, W.WhisperDecodeState):
        return list(ls.cross_kv)
    if isinstance(ls, W.WhisperPagedDecodeState):
        return [ls.cross_k, ls.cross_v, ls.block_table, ls.cross_table]
    return []


def _submit(sched, kind, n, max_new, seed=0):
    if kind == "lm":
        rng = np.random.default_rng(seed)
        return [sched.submit(rng.integers(2, 50, (5,)).astype(np.int32),
                             max_new=max_new) for _ in range(n)]
    return [sched.submit(m, max_new=max_new)
            for m in _mels(sched.engine.cfg, n, seed)]


@pytest.mark.parametrize("kind", POOLS)
def test_step_output_holds_no_read_only_leaf(kind, whisper_setup, lm_setup):
    sched = _scheduler(kind, whisper_setup, lm_setup)
    eng, state = sched.engine, sched.pool.state
    _, _, written = jax.eval_shape(eng._step_jit, eng._serve_params,
                                   sched._tokens, sched._done0, state)
    out_shapes = {l.shape for l in jax.tree_util.tree_leaves(written)}
    read_only = _read_only_leaves(state)
    cross = [l.shape for l in read_only if l.ndim == 5]
    assert (len(cross) == 2) == (kind != "lm")
    assert not out_shapes & set(cross)
    # what comes back is exactly the written part, leaf for leaf
    want = jax.eval_shape(M.step_writes, state)
    assert (jax.tree_util.tree_structure(written)
            == jax.tree_util.tree_structure(want))
    if kind == "lm":                       # an LM step writes everything
        assert (jax.tree_util.tree_structure(written)
                == jax.tree_util.tree_structure(state))


@pytest.mark.parametrize("kind", POOLS)
def test_pool_keeps_read_only_leaves_by_reference(kind, whisper_setup,
                                                  lm_setup):
    sched = _scheduler(kind, whisper_setup, lm_setup)
    _submit(sched, kind, 2, max_new=4)
    sched.admit()
    # the first step uploads the paged pool's host block tables; no page
    # boundary is crossed after it
    sched.decode_step()
    before = _read_only_leaves(sched.pool.state)
    steps_before = sched.pool.state.step
    sched.decode_step()
    sched.decode_step()
    after = _read_only_leaves(sched.pool.state)
    assert len(after) == len(before)
    assert all(a is b for a, b in zip(after, before))
    # the written leaves did move on
    assert np.all(np.asarray(sched.pool.state.step)
                  == np.asarray(steps_before) + 2)


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
def test_fill_and_drain_matches_one_shot(kind, whisper_setup):
    """An admission schedule that fills the pool, drains it to empty and
    refills it with staggered budgets serves, per request, the tokens of a
    batch-1 ``transcribe`` of the same utterance."""
    cfg, params = whisper_setup
    ref = ServeEngine(cfg, params, max_len=MAX_LEN, quant="none", eos_id=-1)
    mels = _mels(cfg, 7, seed=3)
    budgets = [5, 2, 7, 3, 6, 1, 4]
    refs = [ref.transcribe(m, max_new=n)[0].tokens
            for m, n in zip(mels, budgets)]
    sched = _scheduler(kind, whisper_setup, None)
    rids = [sched.submit(m, max_new=n) for m, n in zip(mels[:3], budgets)]
    res = sched.run()                                  # fill, then drain
    assert sched.n_active == 0 and sched.n_queued == 0
    rids += [sched.submit(m, max_new=n)
             for m, n in zip(mels[3:], budgets[3:])]   # refill past width
    res.update(sched.run())
    for rid, want in zip(rids, refs):
        assert res[rid].tokens == want


def test_greedy_loop_state_keeps_cross_kv(whisper_setup):
    """The one-shot loop puts each step's writes back onto its own state:
    its final state holds the prefill's cross-KV arrays and the advanced
    self-KV."""
    cfg, params = whisper_setup
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, quant="none", eos_id=-1)
    mel = jnp.asarray(_mels(cfg, 1)[0])
    _, state = eng._prefill_jit(eng._serve_params, mel)
    r = eng._greedy_loop(state, jnp.ones((1, 1), jnp.int32), 3)
    ls = r["state"].layer_states
    assert all(a is b for a, b in zip(ls.cross_kv,
                                      state.layer_states.cross_kv))
    assert int(r["state"].step) == int(state.step) + 3


@pytest.mark.parametrize("kind", POOLS)
def test_step_bytes_gauges(kind, whisper_setup, lm_setup):
    """Set once with the step plan, from the step program's shapes:
    Whisper writes less than it keeps, an LM keeps nothing; gauging
    costs the step no extra trace."""
    tele = obs.Telemetry()
    sched = _scheduler(kind, whisper_setup, lm_setup, telemetry=tele)
    _submit(sched, kind, 3, max_new=3)
    sched.run()
    assert sched.engine._step_traces == 1
    g = tele.metrics.snapshot()["gauges"]
    written = g["repro_step_written_bytes"][""]
    kept = g["repro_step_kept_bytes"][""]
    total = M.state_kv_bytes(sched.pool.state)
    tokens_done = sched.n_slots * (4 + 1)          # int32 tokens + bools
    assert written == total - kept + tokens_done
    if kind == "lm":
        assert kept == 0
    else:
        assert 0 < written < kept
        assert kept == sum(int(l.size) * l.dtype.itemsize
                           for l in _read_only_leaves(sched.pool.state))


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
@pytest.mark.parametrize("arch", ["whisper-tiny", "whisper-large-v3"])
def test_splice_bytes_gauge(arch, kind):
    """Set once with the pool, from the splice's shapes: an admission
    writes one slot's row (contiguous pool) or the request's pages and
    counters (paged pool) in place, not the whole pool."""
    cfg = get_smoke_config(arch)
    params = M.init_params(jax.random.PRNGKey(1), cfg, 64)
    tele = obs.Telemetry()
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, quant="none", eos_id=-1,
                      telemetry=tele)
    sched = (eng.paged_scheduler(n_slots=2, n_frames=N_FRAMES, page_size=4)
             if kind == "paged" else
             eng.scheduler(n_slots=2, n_frames=N_FRAMES))
    g = tele.metrics.snapshot()["gauges"]
    splice = g["repro_splice_written_bytes"][""]
    pool = sched.pool
    if kind == "paged":
        # every self page of a max_len request, its cross pages, and its
        # (R,) lengths and step counter
        assert splice == (pool.max_pages * pool.page_bytes
                          + pool.n_cross_per_req * pool.cross_page_bytes
                          + 4 * (cfg.num_layers + 1))
    else:
        assert splice == M.state_kv_bytes(pool.state) // sched.n_slots
    assert splice < M.state_kv_bytes(pool.state)
    _submit(sched, kind, 1, max_new=2)
    sched.run()
    g = tele.metrics.snapshot()["gauges"]
    assert g["repro_splice_written_bytes"][""] == splice


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
def test_q8_weight_passes_gauge(kind):
    """Set once with the prefill plan, from its shapes: a 376-frame
    encoder (8 x 47 rows, which the 128-row cap split into 47 tiles of
    8) is one M tile of every Q8_0 matmul, so each weight tile is
    dequantized once per prefill."""
    from repro.core.offload import OffloadEngine
    from repro.tuning import padded_m
    from repro.tuning.space import default_tiles

    frames = 376
    cfg = get_smoke_config("whisper-tiny")
    params = M.init_params(jax.random.PRNGKey(2), cfg, frames)
    tele = obs.Telemetry()
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, quant="q8_0",
                      offload=OffloadEngine(burst=64), eos_id=-1,
                      telemetry=tele)
    sched = (eng.paged_scheduler(n_slots=2, n_frames=frames, page_size=4)
             if kind == "paged" else
             eng.scheduler(n_slots=2, n_frames=frames))
    rng = np.random.default_rng(0)
    sched.submit(rng.standard_normal((1, frames, cfg.n_mels)
                                     ).astype(np.float32), max_new=2)
    sched.run()
    passes = tele.metrics.snapshot()["gauges"]["repro_q8_weight_passes"][""]
    (plan,) = [p for k, p in eng._plans.plans.items() if k[0] == "prefill"]
    q8 = [e for e in plan if e.kernel == "q8_matmul" and e.offload]
    assert max(e.m for e in q8) == frames
    want = max(-(-padded_m(e.m)
                 // default_tiles(e.kernel, padded_m(e.m), e.n, e.k_main)[0])
               for e in q8)
    assert passes == want == 1
