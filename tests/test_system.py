"""System-level smoke: the public API end-to-end on one architecture —
init -> train 3 steps -> checkpoint -> serve with Q8_0 offload."""
import jax
import numpy as np

from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.serve.engine import ServeEngine
from repro.train.trainer import Trainer


def test_train_then_serve_roundtrip(tmp_path):
    cfg = get_smoke_config("qwen2.5-14b")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 4, "train"),
                    optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                              total_steps=10),
                    steps=3, checkpoint_every=2,
                    checkpoint_dir=str(tmp_path / "ck"))
    tr = Trainer(run, vocab_cap=64)
    metrics = tr.train()
    assert np.isfinite(metrics["loss"])

    # serve the trained params through the paper's offload path
    off = OffloadEngine(prefer_pallas=False)
    eng = ServeEngine(cfg, tr.state.params, max_len=32, quant="q8_0",
                      offload=off, eos_id=-1)
    res = eng.generate(np.ones((2, 4), np.int32), max_new=4)
    assert len(res) == 2 and res[0].steps == 4
    assert off.stats.offloaded_calls > 0
    rep = eng.energy_report(res)
    assert rep["pdp_j"] > 0


def test_compile_cache_dir_env_then_fixed(tmp_path, monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when set
    — compiled entries land there — else one fixed directory inside the
    checkout, the same on every call."""
    import os

    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)(jnp.arange(7.0))
        assert os.listdir(tmp_path)              # the entry landed here

        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable() == path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
