"""A run with the timed path broken underneath must come out not
correct, and the int4 control must fail the limit that sound runs meet.
Drives the whole of ``harness.run_cell`` on the CPU (the look for a chip
is ``main``'s) at smoke widths."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench_cells import SMOKE_GAP, smoke_cell
from chip_bench import harness


def alter_tokens(engine):
    """Every produced token replaced by its neighbour in the vocabulary."""
    step = engine._step_jit
    vocab = engine.cfg.vocab_size

    def broken(params, token, done, state):
        nxt, done, state = step(params, token, done, state)
        return (nxt + 1) % vocab, done, state
    engine._step_jit = broken


def freeze_state(engine):
    """The decode step hands back the state it was given."""
    step = engine._step_jit

    def broken(params, token, done, state):
        nxt, done, _ = step(params, token, done, state)
        return nxt, done, state
    engine._step_jit = broken


def run(name, seed, **kw):
    return harness.run_cell(smoke_cell(name), seed, 1.0, False,
                            time.perf_counter(), **kw)


@pytest.mark.parametrize("name", ["whisper-tiny-q8.longform",
                                  "whisper-tiny-q8.commands"])
def test_sound_run_is_correct(name):
    out = run(name, 2**31 + 77)
    res = out["result"]
    assert res["correct"], out["lines"]
    assert res["checks"]["logit_gap"]["value"] <= SMOKE_GAP
    assert list(res)[-1] == "checks"
    assert out["lines"][-1].startswith("check short")


@pytest.mark.parametrize("fault", [alter_tokens, freeze_state],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ["whisper-tiny-q8.longform",
                                  "whisper-tiny-q8.commands"])
def test_broken_path_is_not_correct(name, fault):
    res = run(name, 2**31 + 78, engine_hook=fault)["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > SMOKE_GAP


@pytest.mark.parametrize("seed", [2, 3])
def test_int4_control_fails_the_limit(seed):
    out = run("whisper-tiny-q8.commands", seed, control_bits=4)
    served = max(float(g.max()) for g in out["served_gaps"])
    control = max(float(g.max()) for g in out["control_gaps"])
    assert served <= SMOKE_GAP < control
    # the control's tokens in place of the served ones fail the run's
    # own correct, which the served tokens pass
    assert out["result"]["correct"]
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["logit_gap"]["value"] == control


LIMITS = {"max_logit_gap": 0.1}


@pytest.mark.parametrize("fault", ["logit_gap", "unserved", "short", "none"])
def test_judge_fails_on_each_check(fault):
    """``correct`` needs every compared number within its limit, and a
    sample to compare at all; each check alone turns it false."""
    gaps = [np.array([0.0, 0.02]), np.array([0.5 if fault == "logit_gap"
                                             else 0.1])]
    checks, correct = harness.judge(
        [] if fault == "none" else gaps, int(fault == "unserved"),
        int(fault == "short"), LIMITS)
    assert not correct
    assert list(checks) == ["logit_gap", "unserved", "short"]
    checks, correct = harness.judge(gaps[:1], 0, 0, LIMITS)
    assert correct and checks["logit_gap"]["value"] == 0.02
