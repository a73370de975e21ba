"""The offload ledger counts a layer stack once per layer (DESIGN.md
§10.2): a ``lax.scan`` or ``vmap`` over stacked layers traces its body
once but runs it per layer, so its plan holds one entry per linear per
layer. Hand count at smoke widths: every linear commits 2·M·K·N."""
import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.models import model as M
from repro.serve.engine import ServeEngine

FRAMES = 8
SLOTS = 2


def _flops(ledger) -> int:
    t = ledger.totals
    return t.offloaded_flops + t.fallback_flops + t.residual_flops


def _hand_count(cfg):
    d, f, b = cfg.d_model, FRAMES, SLOTS
    dq = cfg.num_heads * cfg.head_dim
    dkv = cfg.num_kv_heads * cfg.head_dim

    def attn(m):                                  # q, k, v, o
        return 2 * m * (d * dq + 2 * d * dkv + dq * d)

    def ffn(m):
        return 2 * m * 2 * d * cfg.d_ff

    prefill = (2 * f * cfg.n_mels * d                       # frontend
               + cfg.num_encoder_layers * (attn(f) + ffn(f))
               + cfg.num_layers * 2 * 2 * f * d * dkv)      # cross K/V
    step = (cfg.num_layers * (attn(b)                       # self-attn
                              + 2 * b * (d * dq + dq * d)   # cross q, o
                              + ffn(b))
            + 2 * b * d * cfg.padded_vocab)                 # readout
    return prefill, step


@pytest.mark.parametrize("scan_layers", [False, True])
def test_prefill_and_step_commit_every_layer(scan_layers):
    cfg = dataclasses.replace(get_smoke_config("whisper-tiny"),
                              scan_layers=scan_layers)
    params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
    tele = obs.Telemetry()
    off = OffloadEngine(interpret=True, prefer_pallas=False)
    eng = ServeEngine(cfg, params, max_len=16, quant="q8_0", offload=off,
                      eos_id=-1, telemetry=tele)
    sched = eng.scheduler(n_slots=SLOTS, n_frames=FRAMES)
    sched.submit(np.zeros((FRAMES, cfg.n_mels), np.float32), max_new=2)
    want_prefill, want_step = _hand_count(cfg)
    f0 = _flops(off.ledger)
    sched.admit()
    f1 = _flops(off.ledger)
    sched.decode_step()
    f2 = _flops(off.ledger)
    assert (f1 - f0, f2 - f1) == (want_prefill, want_step)
    assert tele.ledger_consistent()["exact"]


def test_repeat_nests_and_counts_eager_calls():
    off = OffloadEngine(interpret=True, prefer_pallas=False)
    x = np.ones((4, 32), np.float32)
    w = np.ones((16, 32), np.float32)
    with off.repeat(3), off.repeat(2):
        off.linear(x, w)
    assert off.ledger.totals.fallback_calls + \
        off.ledger.totals.offloaded_calls == 6
    assert _flops(off.ledger) == 6 * 2 * 4 * 32 * 16
    assert off._repeat == 1
