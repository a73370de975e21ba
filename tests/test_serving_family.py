"""The serving-family seam and the engine's admission API (DESIGN.md §11.4).

Which family a model is — the state it carries, how it prefills, its first
token — is decided once, by ``models.model.family_of``; ``serve/`` asks the
family object and keys, plans, runs and commits its programs only through
``ServeEngine``'s admission API."""
import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.core.plan import plan_key
from repro.models import model as M
from repro.serve.engine import ServeEngine

SERVE = Path(__file__).resolve().parents[1] / "src" / "repro" / "serve"
ENGINE_INTERNALS = {"_key", "_plan", "_prefill_fn", "_decode_fn"}
N_FRAMES = 16
PROMPT = 5


def _serve_trees():
    paths = sorted(SERVE.glob("*.py"))
    assert {p.name for p in paths} >= {"engine.py", "scheduler.py",
                                       "paging.py", "speculative.py"}
    return [(p.name, ast.parse(p.read_text())) for p in paths]


def test_serve_never_tests_the_family():
    found = []
    for name, tree in _serve_trees():
        for node in ast.walk(tree):
            if isinstance(node, ast.Compare) and any(
                    isinstance(side, ast.Attribute) and side.attr == "family"
                    for side in [node.left, *node.comparators]):
                found.append(f"{name}:{node.lineno} compares .family")
            if (isinstance(node, ast.Attribute) and node.attr == "_audio") or (
                    isinstance(node, ast.Name) and node.id == "_audio"):
                found.append(f"{name}:{node.lineno} reads _audio")
    assert not found, found


def test_only_the_engine_reaches_its_program_internals():
    found = [f"{name}:{node.lineno} .{node.attr}"
             for name, tree in _serve_trees() if name != "engine.py"
             for node in ast.walk(tree)
             if isinstance(node, ast.Attribute)
             and node.attr in ENGINE_INTERNALS]
    assert not found, found


@pytest.fixture(scope="module")
def engines():
    built = {}

    def get(arch):
        if arch not in built:
            cfg = get_smoke_config(arch)
            params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
            built[arch] = ServeEngine(cfg, params, max_len=16, quant="none",
                                      offload=OffloadEngine(), eos_id=-1)
        return built[arch]
    return get


def _one_request(cfg, rng):
    if M.family_of(cfg) is M.AUDIO:
        return rng.standard_normal((N_FRAMES - 3, cfg.n_mels)).astype(
            np.float32)
    return rng.integers(2, 50, (PROMPT,)).astype(np.int32)


def _flops(plan):
    return sum(e.flops * n for e, n in plan.counts().items())


@pytest.mark.parametrize("role", ["main", "draft", "verify"])
@pytest.mark.parametrize("arch", ["whisper-tiny", "qwen2.5-14b"])
def test_family_contract(arch, role, engines):
    eng = engines(arch)
    cfg, fam = eng.cfg, eng.family
    audio = cfg.family == "audio"
    assert fam is M.family_of(cfg) is (M.AUDIO if audio else M.TOKEN_LM)
    rng = np.random.default_rng(0)
    one = _one_request(cfg, rng)

    # payload rank: one request, batch-1 or not; padded to the pool's frames
    req = fam.request(one, N_FRAMES)
    assert req.shape == ((1, N_FRAMES, cfg.n_mels) if audio else (1, PROMPT))
    np.testing.assert_array_equal(req[0, :one.shape[0]], one)
    assert not req[0, one.shape[0]:].any()
    np.testing.assert_array_equal(fam.request(one[None], N_FRAMES), req)
    with pytest.raises(ValueError, match="ONE request"):
        fam.request(np.stack([one, one]), N_FRAMES)
    with pytest.raises(ValueError, match="ONE request"):
        fam.request(one[..., 0] if audio else one[None, None], N_FRAMES)
    if audio:
        with pytest.raises(ValueError, match="frames"):
            fam.request(np.zeros((N_FRAMES + 1, cfg.n_mels), np.float32),
                        N_FRAMES)

    # what the family serves
    for what in ("verify", "paged"):
        if audio:
            fam.require(what)
        else:
            with pytest.raises(NotImplementedError, match="audio family"):
                fam.require(what)

    # the prefill commits its plan once per call, under the role given,
    # times the family's multiplicity (1, or once per prompt token)
    ledger = eng.offload.ledger
    payload = jnp.asarray(req)
    key = plan_key("prefill", eng._serve_quant, 1, req.shape[1])
    by_role = dict(ledger.totals.by_role)
    commits = ledger.commits
    for call in (1, 2):
        out, state = eng.prefill(payload, role=role)
        assert ledger.commits == commits + call
    plan = eng._plans.plans[key]
    times = 1 if audio else PROMPT
    assert fam.prefill_times(payload) == times
    after = ledger.totals.by_role
    assert after[role] - by_role.get(role, 0) == 2 * times * _flops(plan) > 0
    assert {r: v for r, v in after.items() if r != role} == {
        r: v for r, v in by_role.items() if r != role}

    # the first token: SOT on the host, or the greedy pick after the prompt
    first = eng.first_token(out, sot_id=7)
    assert first.shape == (1, 1)
    if audio:
        assert isinstance(first, np.ndarray) and first[0, 0] == 7
    else:
        logits = np.asarray(out[:, -1, :cfg.vocab_size])
        assert int(np.asarray(first)[0, 0]) == int(logits.argmax(-1)[0])

    # the plan key's family part: the pool's frame count, or nothing
    extra = (N_FRAMES,) if audio else ()
    assert fam.step_extra(N_FRAMES) == extra
    eng.program_plan(1, state, N_FRAMES, role=role)
    key_role = None if role == "main" else role
    assert plan_key("step", eng._serve_quant, 1, *extra,
                    role=key_role) in eng._plans.plans
