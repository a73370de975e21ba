"""The float32 reference against the served path for whisper-large-v3 at
the repo's smoke widths on the CPU, with its 128 mel channels and its
layer stacks scanned, as served: prefill, then decode through the cache,
on seeded weights, compared as logits."""
from __future__ import annotations

import numpy as np

from chip_bench import reference, spec, weights
from test_bench_reference import TOKENS, served_logits

SMOKE = {"n_mels": 128, "scan_layers": True}


def test_reference_matches_the_served_path_large_v3():
    # float32 smoke widths, so only the weights' Q8_0 rounding differs
    cfg = spec.model_config({"arch": "whisper-large-v3", "smoke": SMOKE})
    assert cfg.n_mels == 128 and cfg.scan_layers
    params = weights.make_params(cfg, 2**33 + 15)
    weights.check_layout(cfg, params)
    mel = np.random.default_rng(1).standard_normal(
        (cfg.encoder_ctx, cfg.n_mels)).astype(np.float32)
    got = served_logits(cfg, params, mel)
    toks = np.asarray(TOKENS, np.int32)
    same_math = np.asarray(reference.logits(reference.quantized(params, 8),
                                            mel, toks, cfg.num_heads))
    scale = np.abs(same_math).max()
    # the reference on the served path's own Q8_0 values: float32 rounding
    assert np.abs(got - same_math).max() <= 1e-4 * scale
    plain = np.asarray(reference.logits(params, mel, toks, cfg.num_heads))
    # on the unrounded weights: Q8_0 moves logits by about 1% of their range
    err = np.abs(got - plain).max()
    assert 1e-5 * scale < err <= 0.05 * scale
    # the int4 control moves them several times further: the comparison
    # tells the served precision from the next one below it
    int4 = np.asarray(reference.logits(reference.quantized(params, 4),
                                       mel, toks, cfg.num_heads))
    assert np.abs(int4 - plain).max() > 3 * err
    gaps = reference.served_gaps(plain, got[:, :cfg.vocab_size].argmax(-1),
                                 cfg.vocab_size)
    assert gaps.max() <= 0.05 * scale
