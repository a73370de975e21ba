"""The pool's splices update it in place (DESIGN.md §11.1, §15.2): every
jitted program that maps a pool state to a new one donates the state, so
XLA aliases the whole pool to the output and writes only what changes,
and the state handed in is deleted by the call. The splices themselves
stay pure (tests/test_scheduler.py compares their result with the pool
they were given)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import model as M
from repro.serve import speculative
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import SlotKVPool
from repro.serve.paging import PagedKVPool

N_FRAMES = 48
MAX_LEN = 16
# (pool kind, splice): every pool-to-pool program of the serving path
SPLICES = (("contiguous", "insert"), ("contiguous", "reset"),
           ("contiguous", "rollback"), ("paged", "insert"),
           ("paged", "attach"), ("paged", "copy"))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("whisper-tiny")
    params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, quant="none", eos_id=-1)
    mel = np.random.default_rng(0).standard_normal(
        (1, N_FRAMES, cfg.n_mels)).astype(np.float32)
    _, req = eng._prefill_jit(eng._serve_params, jnp.asarray(mel))
    return cfg, params, req


def _pool(kind, cfg, params):
    if kind == "paged":
        pool = PagedKVPool(cfg, params, n_slots=2, max_len=MAX_LEN,
                           n_frames=N_FRAMES, page_size=4)
        slot = pool.acquire()
        pool.alloc_cross_pages(slot, "d0")
        pool.alloc_self_page(slot)
        pool.sync()
        return pool
    return SlotKVPool(cfg, params, n_slots=2, max_len=MAX_LEN,
                      n_frames=N_FRAMES)


def _call(kind, op, pool, req):
    """The jit, its arguments and its keywords for one splice of slot 0."""
    if op == "insert" and kind == "paged":
        return pool._insert_jit, (pool.state, 0, jnp.asarray(pool._bt[0]),
                                  jnp.asarray(pool._ct[0]), req), \
            {"write_cross": True}
    if op == "insert":
        return pool._insert_jit, (pool.state, 0, req), {}
    if op == "reset":
        return pool._reset_jit, (pool.state, 0), {}
    if op == "rollback":
        return speculative._rollback, (pool.state,
                                       jnp.zeros((2,), jnp.int32)), {}
    if op == "attach":
        return pool._attach_jit, (pool.state, 0), {}
    return pool._copy_jit, (pool.state, 1, 2), {}


@pytest.mark.parametrize("kind,op", SPLICES)
def test_splice_aliases_the_whole_pool(kind, op, setup):
    """The compiled splice's output aliases every leaf of the pool state,
    counters included; a splice that copied the pool would alias none."""
    cfg, params, req = setup
    pool = _pool(kind, cfg, params)
    fn, args, kw = _call(kind, op, pool, req)
    mem = fn.lower(*args, **kw).compile().memory_analysis()
    assert mem.alias_size_in_bytes == M.state_kv_bytes(pool.state)


@pytest.mark.parametrize("kind,op", SPLICES)
def test_splice_deletes_the_state_it_was_given(kind, op, setup):
    """Donation engaged: after the splice the old state's leaves are
    deleted, and the state the call returned is whole and readable."""
    cfg, params, req = setup
    pool = _pool(kind, cfg, params)
    before = jax.tree_util.tree_leaves(pool.state)
    fn, args, kw = _call(kind, op, pool, req)
    pool.state = fn(*args, **kw)
    assert all(leaf.is_deleted() for leaf in before)
    after = jax.tree_util.tree_leaves(pool.state)
    assert [a.shape for a in after] == [b.shape for b in before]
    assert not any(leaf.is_deleted() for leaf in after)
    jax.block_until_ready(pool.state)


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
def test_insert_writes_the_row_and_keeps_the_rest(kind, setup):
    """``pool.insert`` in place: slot 0 reads back the request's cross-KV
    and counters, and the other slot's row is what it was."""
    cfg, params, req = setup
    pool = _pool(kind, cfg, params)
    ls = pool.state.layer_states
    if kind == "paged":
        other = np.asarray(ls.cross_k[:, 0])           # the trash page
        pool.insert(0, req)
        ls = pool.state.layer_states
        got = np.asarray(ls.cross_k[:, pool._ct[0]]).reshape(
            cfg.num_layers, N_FRAMES, cfg.num_kv_heads, cfg.head_dim)
        kept = np.asarray(ls.cross_k[:, 0])
    else:
        other = np.asarray(ls.cross_kv[0][:, 1])
        pool.insert(0, req)
        ls = pool.state.layer_states
        got = np.asarray(ls.cross_kv[0][:, 0])
        kept = np.asarray(ls.cross_kv[0][:, 1])
    want = np.asarray(req.layer_states.cross_kv[0][:, 0]).astype(got.dtype)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(kept, other)
    assert int(pool.state.step[0]) == int(req.step)
