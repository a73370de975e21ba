"""Compile rehearsals for a TPU v5e with no chip attached (DESIGN.md §6.3).

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology. These tests compile the main-path kernels at
Whisper-tiny, -base, -small and -large-v3 widths — every weight GEMM of
the model, the tied vocab readout included — with the tilings the offload
engine's plan and the untuned defaults resolve (the 1504-row encoder as
one M tile of the Q8_0 matmul), and the first candidates
the autotuner's space emits; whisper-tiny's served prefill and slot step
with the native kernels; whisper-large-v3's served prefill, slot step
and slot splice at its benchmark deployment, with what they hold in HBM;
and the slot splice at each benchmark deployment, updating the pool in
place.
Nothing runs: a pass says the chip's compiler accepts the program, not
that its results are right (the interpret-mode parity tests say that).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under pytest-xdist
every worker imports this file."""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends.pallas_tpu import bf16_main, m_tiles, q8_main
from repro.configs.registry import get_config
from repro.core.offload import OffloadEngine
from repro.core.qformats import QBLOCK, QTensor
from repro.kernels.bf16_matmul import bf16_matmul
from repro.kernels.q8_matmul import q8_matmul
from repro.kernels.q8_matvec import q8_matvec
from repro.tuning import enumerate_candidates

ARCHS = ("whisper-tiny", "whisper-base", "whisper-small",
         "whisper-large-v3")
# (kernel the plan must resolve, activation rows M, quantized weights):
# decode batches pad to 8 and 16 rows; 1504 is the 1500-frame encoder
VARIANTS = (("q8_matvec", 8, True), ("q8_matvec", 16, True),
            ("q8_matmul", 1504, True), ("bf16_matmul", 1504, False))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    assert "tpu_custom_call" in compiled.as_text()


def _deployment(name):
    """(arch, n_slots, max_len) of a benchmark configuration."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chip_bench", "configs",
                           f"{name}.json")) as f:
        spec = json.load(f)
    dep = spec["deployment"]
    return spec["arch"], dep["n_slots"], dep["max_len"]


def _laid_out_bytes(tree):
    """Bytes ``tree`` takes on the chip, in its tiled layouts."""
    return jax.jit(lambda t: jnp.zeros(()), keep_unused=True).lower(
        tree).compile().memory_analysis().argument_size_in_bytes


def _weight_shapes(cfg):
    """(N, K) of every weight GEMM: the (d, d) attention projections, FFN
    up and down, the tied vocab readout, and the fused qkv / cross-K/V
    widths of whisper.cpp's graph (``coverage.enumerate_whisper``)."""
    d, f = cfg.d_model, cfg.d_ff
    return ((3 * d, d), (d, d), (2 * d, d), (f, d), (d, f),
            (cfg.padded_vocab, d))


def _q8_operands(m, n, k):
    return ((m, k), jnp.bfloat16), ((n, k // QBLOCK, QBLOCK), jnp.int8), \
        ((n, k // QBLOCK), jnp.float32)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("kernel,m,quantized", VARIANTS)
def test_main_segments_compile(one_chip, no_persistent_cache, arch, kernel,
                               m, quantized):
    cfg = get_config(arch)
    eng = OffloadEngine()
    for n, k in _weight_shapes(cfg):
        e = eng.plan_entry(m, k, n, quantized=quantized, name=f"{n}x{k}")
        assert e.kernel == kernel and e.k_main
        if kernel == "q8_matmul":
            # the untuned tiles take the whole 1504-row window as one M
            # tile, as the served prefill does
            assert e.tiling is None and m_tiles(e) == 1
        kw = dict(interpret=False, block_k=256, tiling=e.tiling)
        if quantized:
            _compile(one_chip,
                     lambda x, q, s: q8_main(x, QTensor(q, s), **kw),
                     *_q8_operands(m, n, e.k_main))
        else:
            _compile(one_chip, functools.partial(bf16_main, **kw),
                     ((m, e.k_main), jnp.bfloat16),
                     ((n, e.k_main), jnp.bfloat16))


@pytest.mark.parametrize("kernel", ["q8_matvec", "q8_matmul", "bf16_matmul"])
def test_tuner_candidates_compile(one_chip, no_persistent_cache, kernel):
    """The first candidates of the autotuner's space for whisper-tiny's
    FFN up projection (in the order the tuner ranks ties)."""
    m = 8 if kernel == "q8_matvec" else 1504
    n, k = 1536, 384
    cands = enumerate_candidates(kernel, m, n, k)[:3]
    assert cands
    for c in cands:
        kw = dict(c.as_kwargs(), interpret=False)
        if kernel == "bf16_matmul":
            _compile(one_chip, functools.partial(bf16_matmul, **kw),
                     ((m, k), jnp.bfloat16), ((n, k), jnp.bfloat16))
            continue
        fn = q8_matvec if kernel == "q8_matvec" else q8_matmul
        x, q, s = _q8_operands(m, n, k)
        _compile(one_chip, functools.partial(fn, **kw),
                 x, ((n, k), jnp.int8), s)


def test_whisper_tiny_served_programs_compile(one_chip, no_persistent_cache):
    """whisper-tiny's batch-1 prefill at 1500 frames and its 4-slot decode
    step, traced as on the chip (main segments on native pallas_tpu). The
    step returns only the state it writes (DESIGN.md §11.2): its outputs
    are smaller than the cross-KV it reads, so XLA copies none of it."""
    from repro.backends import platform
    from repro.models import model as model_lib
    from repro.serve.engine import ServeEngine

    cfg = get_config("whisper-tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg, 448)
    eng = ServeEngine(cfg, params, max_len=24, quant="q8_0",
                      offload=OffloadEngine(), eos_id=None)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    p = on_chip(eng._serve_params)
    mel = (cfg.encoder_ctx, cfg.n_mels)
    state = on_chip(jax.eval_shape(
        lambda pp, mm: eng._prefill_fn(pp, mm)[1], p,
        jax.ShapeDtypeStruct((4, *mel), jnp.float32)))
    cross_kv_bytes = model_lib.state_kv_bytes(state.layer_states.cross_kv)
    platform._PROBE["platform"] = "tpu"      # route as the chip would
    try:
        for fn, args in (
                (eng._prefill_jit,
                 (p, jax.ShapeDtypeStruct((1, *mel), jnp.float32,
                                          sharding=one_chip))),
                (eng._step_jit,
                 (p, *on_chip((jax.ShapeDtypeStruct((4, 1), jnp.int32),
                               jax.ShapeDtypeStruct((4,), bool))), state))):
            compiled = fn.lower(*args).compile()
            mem = compiled.memory_analysis()
            assert mem is not None
            assert "tpu_custom_call" in compiled.as_text()
        assert mem.output_size_in_bytes < cross_kv_bytes
    finally:
        platform.reset_probe_cache()


def test_whisper_large_v3_served_programs_fit(one_chip, no_persistent_cache):
    """whisper-large-v3 at published widths and the benchmark's slot
    count: its batch-1 prefill (32-layer encoder scan, K = 5120 down
    projection), its decode step over the pool, and the slot splice
    compile as on the chip. Built from shapes alone (nothing is
    allocated): the Q8_0 weights and the pool, which the splice updates
    in place, fit one chip's 16 GiB."""
    from repro.backends import platform
    from repro.core.qformats import quantize_tree
    from repro.models import model as model_lib
    from repro.serve import kvcache
    from repro.serve.engine import ServeEngine, _keep_dense

    arch, n, max_len = _deployment("whisper-large-v3-q8")
    cfg = get_config(arch)
    dense = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg, 448), jax.random.PRNGKey(0))
    served = jax.eval_shape(lambda p: quantize_tree(p, _keep_dense), dense)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    # the weights are already Q8_0 shapes: the engine serves them as given
    eng = ServeEngine(cfg, served, max_len=max_len, quant="none",
                      offload=OffloadEngine(), eos_id=None)
    p = on_chip(served)
    mel = (cfg.encoder_ctx, cfg.n_mels)
    dtype = model_lib._dtype(cfg)
    pool = on_chip(jax.eval_shape(
        lambda pp: model_lib.slot_layout(model_lib.init_serve_state(
            pp, cfg, n, max_len,
            memory=jnp.zeros((n, cfg.encoder_ctx, cfg.d_model), dtype)), n),
        served))
    req = on_chip(jax.eval_shape(
        lambda pp, mm: eng._prefill_fn(pp, mm)[1], p,
        jax.ShapeDtypeStruct((1, *mel), jnp.float32)))
    platform._PROBE["platform"] = "tpu"      # route as the chip would
    try:
        mems = {}
        for name, fn, args in (
                ("prefill", eng._prefill_jit,
                 (p, jax.ShapeDtypeStruct((1, *mel), jnp.float32,
                                          sharding=one_chip))),
                ("step", eng._step_jit,
                 (p, *on_chip((jax.ShapeDtypeStruct((n, 1), jnp.int32),
                               jax.ShapeDtypeStruct((n,), bool))), pool)),
                ("splice", kvcache._INSERT_JIT,
                 (pool, jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=one_chip), req))):
            compiled = fn.lower(*args).compile()
            mems[name] = compiled.memory_analysis()
            assert mems[name] is not None
            if name != "splice":
                assert "tpu_custom_call" in compiled.as_text()
    finally:
        platform.reset_probe_cache()
    pool_bytes = model_lib.state_kv_bytes(pool)
    step, splice = mems["step"], mems["splice"]
    # the splice's output is the pool it was given (laid out in the
    # chip's tiles); the step returns the self-KV, under a sixth of one
    # (DESIGN.md §11.2)
    laid_out = _laid_out_bytes(pool)
    assert pool_bytes <= laid_out < 1.1 * pool_bytes
    assert splice.alias_size_in_bytes == laid_out
    assert step.output_size_in_bytes < pool_bytes // 6
    # the weights as laid out on the chip
    weights = _laid_out_bytes(p)
    assert weights >= model_lib.state_kv_bytes(served)
    # held at once: the weights, the one pool and the splice's scratch
    held = weights + laid_out + splice.temp_size_in_bytes
    assert held < 16 * 2**30


@pytest.mark.parametrize("name", ["whisper-tiny-q8", "whisper-small-q8",
                                  "whisper-large-v3-q8"])
def test_splice_updates_the_pool_in_place(one_chip, no_persistent_cache,
                                          name):
    """The pool's splice at each benchmark deployment, compiled for the
    chip: its output aliases the whole pool as laid out, it needs no
    scratch, and no leaf of the pool is copied (DESIGN.md §11.1)."""
    from repro.models import model as model_lib
    from repro.serve import kvcache

    arch, n, max_len = _deployment(name)
    cfg = get_config(arch)
    dtype = model_lib._dtype(cfg)
    params = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg, 448), jax.random.PRNGKey(0))

    def state(b):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda pp: model_lib.slot_layout(
                model_lib.init_serve_state(
                    pp, cfg, b, max_len, memory=jnp.zeros(
                        (b, cfg.encoder_ctx, cfg.d_model), dtype)), b),
                params))

    pool = state(n)
    compiled = kvcache._INSERT_JIT.lower(
        pool, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        state(1)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _laid_out_bytes(pool)
    assert mem.temp_size_in_bytes == 0
    leaves = {f"[{','.join(map(str, a.shape))}]"
              for a in jax.tree_util.tree_leaves(pool)}
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"\bcopy(-start)?\(", line)]
    assert not [c for c in copies if any(leaf in c for leaf in leaves)]
