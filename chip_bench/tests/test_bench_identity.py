"""The Whisper family's weights, reference, requests and FLOPs read what
the harness read before the family seam existed: every constant here was
recorded from that code, at the sizes below, on the CPU. A seed must give
the same weights and the same audio, and the reference the same logits,
so that a cell's ``checks`` read the same values for the same seed."""
from __future__ import annotations

import functools
import hashlib
import os

import jax
import numpy as np
import pytest

from bench_cells import SMOKE, WHISPER
from chip_bench import spec, traffic
from repro.configs.registry import get_config

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")

#: sha256 over the sorted lines "<leaf path> <sha256 of the leaf's bytes,
#: 16 hex digits>" of the bench's smoke-width (bf16) weights
WEIGHTS = {
    ("whisper-tiny", 2**33 + 5):
        "83e230e51414f0601bddd522470f0660df32e2becf7bd1adaf14742ab0f3c8bd",
    ("whisper-tiny", 7):
        "1ed4cc9ffb84afba3b5856b88948e5893a47efe4321aff6bbe8d0a4958ec2d2a",
    ("whisper-large-v3", 2**33 + 5):
        "f93dec8152944385016af9ac197a4e54dd49efc03fba613737bdc3f1d1674051",
    ("whisper-large-v3", 7):
        "f577d4be0ba7d270e0bca9a574e6820f454e7b29532e74695dfe35bb7ac5044d",
}

#: the first requests of a run with seed 2**40 + 3 and 51 s, as the cells
#: resolve their mixes: (index, size, max_new, due, sha256 of the audio),
#: and sha256 of every size and every output length of the run (int64)
TRAFFIC = {
    "whisper-small-q8.commands": {
        "n": 898,
        "first": [
            (0, 111, 19, 0.06435091430639184, "76557d6ae80e5f20f83e5db8a90b"
             "855f50ece9f3659b3d55c40f6db1cc811df5"),
            (1, 135, 20, 0.22531652473722974, "3e41492ba1809bddf358d104e7fb"
             "14827bf89ee8dfe103f9d2164bd0884779f3"),
            (2, 150, 18, 0.2502916640097317, "75702be0ac6acba6ac938765da00"
             "b02db71138775df8fc274ed8024538911f32"),
            (3, 224, 11, 0.31420377232771557, "acc33a4113e371a2854a5b9ff3bd"
             "3ef21e281a9c01154a22ffb0cd581fb7688d")],
        "sizes": "caf9b13f564849cb7b3b653d1b94d080904cb2f403ffddbe9764dd7aae0357d8",
        "max_new": "8d2308e69f44521796a67321c78c0fe25dc290a9fe5eac59209f7bb98668062f",
    },
    "whisper-tiny-q8.longform": {
        "n": 256,
        "first": [
            (0, 1500, 128, None, "4eed8fe320d65a7c06dd36e970af21c9f44b7588"
             "0cf95bd2e4ab512ed803a8ec"),
            (1, 1500, 48, None, "8ee2e1f30f9c2722e7ef49819e53bfa1ea7f18bc88"
             "26a0e91c7f38dc3350b279"),
            (2, 1500, 50, None, "3260265a5c3b90c3bfb3b81542522d3b61beb745d1"
             "572d67984ff6005b60de77"),
            (3, 1500, 100, None, "e366baea1499a299f82c86f763cac8f11a31904e7e"
             "1d479e96df279d812d0370")],
        "sizes": "c50d0acaeba05f4048a29c485ba1ea1f4db9c99a6ae218b74cca3f0d6384e150",
        "max_new": "767a819ab0eaadc1762749dcba9c163763cebe06b2b4a08c1771b9b24c1cc932",
    },
}

#: prefill over the 1500-frame window, and a token at contexts 1 and 224
FLOPS = {
    "whisper-tiny": (38_688_768_000, 65_569_536, 66_939_648),
    "whisper-small": (380_399_616_000, 333_178_368, 341_399_040),
    "whisper-large-v3": (2_571_141_120_000, 1_846_707_200, 1_883_243_520),
}


def _sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


@pytest.mark.parametrize("arch,seed", list(WEIGHTS))
def test_weights_are_the_recorded_ones(arch, seed):
    smoke = dict(SMOKE, n_mels=128) if arch == "whisper-large-v3" else SMOKE
    cfg = spec.model_config({"arch": arch, "smoke": smoke})
    flat = jax.tree_util.tree_flatten_with_path(
        WHISPER.make_params(cfg, seed))[0]
    lines = sorted(f"{jax.tree_util.keystr(p)} "
                   f"{_sha(np.asarray(leaf).tobytes())[:16]}"
                   for p, leaf in flat)
    assert len(lines) == 44
    assert _sha("\n".join(lines).encode()) == WEIGHTS[(arch, seed)]


def test_reference_logits_are_the_recorded_ones():
    cfg = spec.model_config({"arch": "whisper-tiny", "smoke": {}})
    p = WHISPER.make_params(cfg, 2**33 + 5)
    mel = np.random.default_rng(0).standard_normal(
        (cfg.encoder_ctx, cfg.n_mels)).astype(np.float32)
    toks = np.asarray([1, 5, 17, 200, 3, 499, 42], np.int32)
    got = np.asarray(WHISPER.logits(p, mel, toks, cfg.num_heads))
    want = np.load(os.path.join(DATA, "whisper_tiny_reference_logits.npy"))
    # the same float32 arithmetic: only a CPU that orders float32 sums
    # otherwise may move the last bits (1e-6 of the logits' range); a
    # changed operation moves them by 1e-3 or more
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # and reference_logits feeds SOT and the served tokens, as before
    rows, served = WHISPER.reference_logits(p, cfg, mel, WHISPER.SOT,
                                            toks[1:].tolist() + [9], 7)
    assert served.tolist() == toks[1:].tolist() + [9]
    np.testing.assert_allclose(rows, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("cell", list(TRAFFIC))
def test_requests_are_the_recorded_ones(cell):
    c = spec.resolve(spec.load_spec(), cell)
    conf = c["config"]
    cfg = spec.model_config(conf)
    tr = traffic.Traffic(c["traffic"], 2**40 + 3, 51.0,
                         conf["deployment"]["n_slots"],
                         functools.partial(WHISPER.payload, cfg=cfg))
    if tr.kind == "backlog":
        tr.prepare(5)
    else:
        tr.prepare()
    want = TRAFFIC[cell]
    assert len(tr.requests) == want["n"]
    got = [(r.index, r.size, r.max_new, r.due, _sha(r.payload.tobytes()))
           for r in tr.requests[:4]]
    assert got == want["first"]
    assert _sha(np.asarray([r.size for r in tr.requests],
                           np.int64).tobytes()) == want["sizes"]
    assert _sha(np.asarray([r.max_new for r in tr.requests],
                           np.int64).tobytes()) == want["max_new"]


@pytest.mark.parametrize("arch", list(FLOPS))
def test_flops_are_the_recorded_ones(arch):
    cfg = get_config(arch)
    got = (WHISPER.prefill_flops(cfg, cfg.encoder_ctx),
           WHISPER.token_flops(cfg, 1, cfg.encoder_ctx),
           WHISPER.token_flops(cfg, 224, cfg.encoder_ctx))
    assert got == FLOPS[arch]
