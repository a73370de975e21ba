"""The decoder-only family's float32 reference against the program's
served path at the repo's smoke widths on the CPU: the prompt's prefill,
then decoding through the cache, on seeded weights, compared as logits
with the reference's full forward pass over the prompt and the tokens."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench import reference, spec, weights
from repro.core.offload import OffloadEngine
from repro.serve.engine import ServeEngine
from test_bench_reference import TOKENS

LM = spec.load_family("decoder_lm")

#: dense decoders of the registry at their smoke widths (float32, 2
#: layers, d 64, 4 query heads), and one with the other branches of the
#: block: LayerNorm, GELU and the tied readout
CASES = {
    "qwen2.5-14b": ("qwen2.5-14b", {}),          # GQA 4:1, q/k/v bias
    "gqa-2-groups": ("qwen2.5-14b", {"num_kv_heads": 2}),  # GQA 4:2
    "internlm2-20b": ("internlm2-20b", {}),      # GQA 4:1, no bias
    "phi3-mini-3.8b": ("phi3-mini-3.8b", {}),    # MHA
    "layernorm-gelu-tied": ("qwen2.5-14b", {"norm": "layernorm",
                                            "act": "gelu",
                                            "tie_embeddings": True}),
}


def served_rows(cfg, params, prompt):
    """The served path's logits: the prefill's after the prompt, then the
    decode step's after each of ``TOKENS`` fed through the cache."""
    engine = ServeEngine(cfg, params, max_len=16, quant="q8_0",
                         offload=OffloadEngine(), eos_id=None)
    last, state = engine._prefill_jit(engine._serve_params,
                                      jnp.asarray(prompt[None]))
    rows = [np.asarray(last, np.float32).reshape(-1)]
    for t in TOKENS:
        logits, state = engine._decode_jit(
            engine._serve_params, jnp.full((1, 1), t, jnp.int32), state)
        rows.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(rows[:-1])


@pytest.mark.parametrize("case", list(CASES))
def test_reference_matches_the_served_path(case):
    arch, extra = CASES[case]
    cfg = spec.model_config({"arch": arch, "smoke": extra})
    params = LM.make_params(cfg, 2**33 + 5)
    weights.check_layout(cfg, params, LM.POSITIONS)
    prompt = LM.payload(3, 0, 8, cfg)
    got = served_rows(cfg, params, prompt)
    seq = np.concatenate([prompt, np.asarray(TOKENS, np.int32)])

    def ref(p):
        return np.asarray(LM.logits(p, seq, cfg))[len(prompt) - 1:-1]
    same_math = ref(reference.quantized(params, 8))
    scale = np.abs(same_math).max()
    # the reference on the served path's own Q8_0 values: float32 rounding
    # (1e-6 of the range read; a wrong rotation, head grouping or norm
    # moves logits by a tenth of it)
    assert np.abs(got - same_math).max() <= 1e-4 * scale
    plain = ref(params)
    # on the unrounded weights: Q8_0 moves logits by 1-4% of their range
    err = np.abs(got - plain).max()
    assert 1e-5 * scale < err <= 0.05 * scale
    # the int4 control moves them over ten times further (read 12-20x):
    # the comparison tells the served precision from the next one below
    int4 = ref(reference.quantized(params, 4))
    assert np.abs(int4 - plain).max() > 3 * err
    # reference_logits reads the same rows for a served stream
    rows, served = LM.reference_logits(params, cfg, prompt, TOKENS[0],
                                       TOKENS[1:], 8)
    assert served.tolist() == TOKENS
    np.testing.assert_allclose(rows, plain, rtol=0, atol=1e-6 * scale)


def test_weights_follow_the_seed_and_the_program_layout():
    cfg = spec.model_config({"arch": "qwen2.5-14b", "smoke": {}})
    a = LM.make_params(cfg, 2**31 + 1)
    b = LM.make_params(cfg, 2**31 + 1)
    c = LM.make_params(cfg, 7)
    wa, wb, wc = (np.asarray(x["stack"]["blocks"][0]["ffn"]["gate"]["w"])
                  for x in (a, b, c))
    assert (wa == wb).all() and not (wa == wc).all()
    weights.check_layout(cfg, a, LM.POSITIONS)
    with pytest.raises(ValueError):
        weights.check_layout(
            spec.model_config({"arch": "qwen2.5-14b",
                               "smoke": {"qkv_bias": False}}),
            a, LM.POSITIONS)


def test_only_the_dense_decoder_is_computed():
    for arch in ("olmoe-1b-7b", "mamba2-780m", "jamba-v0.1-52b"):
        cfg = spec.model_config({"arch": arch, "smoke": {}})
        with pytest.raises(ValueError, match="dense"):
            LM.make_params(cfg, 1)


def test_flops_hand_count():
    cfg = spec.model_config({"arch": "qwen2.5-14b", "smoke": {}})
    d, qd, kvd, f, v, n = 64, 64, 16, 128, 512, 2
    proj = 2 * d * (qd + 2 * kvd) + 2 * qd * d
    mlp = 2 * 3 * d * f                         # SwiGLU: gate, up, down
    # the 3rd token after a 5-token prompt attends 8 positions
    assert LM.token_flops(cfg, 3, 5) == n * (proj + 4 * 8 * qd + mlp) \
        + 2 * d * v
    # a 5-token prompt: positions attending 1..5, one readout
    assert LM.prefill_flops(cfg, 5) == n * (5 * (proj + mlp)
                                            + 4 * qd * 15) + 2 * d * v
