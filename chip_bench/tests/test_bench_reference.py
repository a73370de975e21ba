"""The float32 reference against the served path at the repo's smoke
widths on the CPU: prefill, then decode through the cache, on seeded
weights."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench_cells import WHISPER
from chip_bench import reference, spec, weights
from repro.core.offload import OffloadEngine
from repro.serve.engine import ServeEngine

TOKENS = [1, 5, 17, 200, 3, 499, 42]


def served_logits(cfg, params, payload):
    """The served path's logits after each of ``TOKENS``, fed one at a
    time through the cache after the prefill of ``payload``."""
    engine = ServeEngine(cfg, params, max_len=16, quant="q8_0",
                         offload=OffloadEngine(), eos_id=None)
    _, state = engine._prefill_jit(engine._serve_params,
                                   jnp.asarray(payload[None]))
    out = []
    for t in TOKENS:
        logits, state = engine._decode_jit(
            engine._serve_params, jnp.full((1, 1), t, jnp.int32), state)
        out.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(out)


def test_reference_matches_the_served_path():
    # float32 smoke widths, so only the weights' Q8_0 rounding differs
    cfg = spec.model_config({"arch": "whisper-tiny", "smoke": {}})
    params = WHISPER.make_params(cfg, 2**33 + 5)
    weights.check_layout(cfg, params, WHISPER.POSITIONS)
    mel = np.random.default_rng(0).standard_normal(
        (cfg.encoder_ctx, cfg.n_mels)).astype(np.float32)
    got = served_logits(cfg, params, mel)
    toks = np.asarray(TOKENS, np.int32)
    same_math = np.asarray(WHISPER.logits(reference.quantized(params, 8),
                                          mel, toks, cfg.num_heads))
    scale = np.abs(same_math).max()
    # the reference on the served path's own Q8_0 values: float32 rounding
    assert np.abs(got - same_math).max() <= 1e-4 * scale
    plain = np.asarray(WHISPER.logits(params, mel, toks, cfg.num_heads))
    # on the unrounded weights: Q8_0 moves logits by about 1% of their range
    err = np.abs(got - plain).max()
    assert 1e-5 * scale < err <= 0.05 * scale
    gaps = reference.served_gaps(plain, got[:, :cfg.vocab_size].argmax(-1),
                                 cfg.vocab_size)
    assert gaps.max() <= 0.05 * scale


def test_weights_follow_the_seed():
    cfg = spec.model_config({"arch": "whisper-tiny", "smoke": {}})
    a = WHISPER.make_params(cfg, 2**31 + 1)
    b = WHISPER.make_params(cfg, 2**31 + 1)
    c = WHISPER.make_params(cfg, 7)
    wa, wb, wc = (np.asarray(x["dec_blocks"]["ffn"]["up"]["w"])
                  for x in (a, b, c))
    assert (wa == wb).all() and not (wa == wc).all()
    assert np.allclose(np.asarray(a["enc_pos"]["table"])[:, 0],
                       np.sin(np.arange(WHISPER.POSITIONS)), atol=1e-6)


def test_int4_control_moves_logits_more_than_q8():
    cfg = spec.model_config({"arch": "whisper-tiny", "smoke": {}})
    p = WHISPER.make_params(cfg, 11)
    w = p["dec_blocks"]["ffn"]["up"]["w"]
    e8 = np.abs(np.asarray(reference.quantize_blocks(w, 8) - w)).mean()
    e4 = np.abs(np.asarray(reference.quantize_blocks(w, 4) - w)).mean()
    assert 10 < e4 / e8 < 30
