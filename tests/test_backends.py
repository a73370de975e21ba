"""Backend registry (DESIGN.md §12): capability resolution, forcing,
xla_ref-vs-pallas_tpu numerical parity, plan pinning round-trips, and the
single-probe platform-detection invariant."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import (
    RESIDUAL, REGISTRY, KernelRequest, executor, pin_for_prefer)
from repro.backends.registry import FORCE_ENV, BackendRegistry
from repro.configs.registry import get_smoke_config
from repro.core.offload import OffloadEngine
from repro.core.plan import plan_linear, record_plan
from repro.core.qformats import quantize_q8_0
from repro.kernels import ref
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.tuning import kernel_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_forcing(monkeypatch):
    """These tests exercise pin/force semantics themselves — a
    REPRO_BACKEND set by the environment (the CI xla_ref matrix leg) must
    not leak in underneath them."""
    monkeypatch.delenv(FORCE_ENV, raising=False)


def _req(kernel="q8_matmul", m=32, n=64, k=64, dtype="q8_0", **kw):
    return KernelRequest(kernel=kernel, m=m, n=n, k=k, dtype=dtype, **kw)


# ---------------------------------------------------------------------------
# Capability resolution
# ---------------------------------------------------------------------------
def test_builtin_registration_order():
    """Registration order IS resolution priority (DESIGN.md §12.2)."""
    assert REGISTRY.names() == ("pallas_tpu", "host_residual", "xla_ref")


def test_main_segment_resolves_platform_default():
    """Off-TPU, an unpinned main segment lands on xla_ref — the old
    pallas-on-TPU/XLA-elsewhere rule restated as capability resolution."""
    b = REGISTRY.resolve(_req())
    assert b.name == ("pallas_tpu" if jax.default_backend() == "tpu"
                      else "xla_ref")


def test_residual_always_resolves_host():
    assert REGISTRY.resolve(_req(k=17, dtype="bf16",
                                 segment=RESIDUAL)).name == "host_residual"


def test_pin_overrides_capability_order():
    assert REGISTRY.resolve(_req(), pin="pallas_tpu").name == "pallas_tpu"
    assert REGISTRY.resolve(_req(), pin="xla_ref").name == "xla_ref"


def test_unsupported_pin_falls_through():
    """pallas_tpu declines residual segments; the pin falls through to
    capability order rather than erroring."""
    req = _req(k=17, dtype="bf16", segment=RESIDUAL)
    assert REGISTRY.resolve(req, pin="pallas_tpu").name == "host_residual"


def test_prefer_pallas_translation():
    assert pin_for_prefer(True) == "pallas_tpu"
    assert pin_for_prefer(False) == "xla_ref"
    assert pin_for_prefer(None) is None


def test_unknown_backend_raises():
    with pytest.raises(KeyError):
        REGISTRY.get("cgla_sim")
    with pytest.raises(KeyError):
        with REGISTRY.force("cgla_sim"):
            pass


def test_force_context_beats_pin():
    with REGISTRY.force("xla_ref"):
        assert REGISTRY.resolve(_req(), pin="pallas_tpu").name == "xla_ref"
    # restored on exit
    assert REGISTRY.resolve(_req(), pin="pallas_tpu").name == "pallas_tpu"


def test_force_env_var(monkeypatch):
    monkeypatch.setenv(FORCE_ENV, "xla_ref")
    assert REGISTRY.resolve(_req(), pin="pallas_tpu").name == "xla_ref"
    monkeypatch.setenv(FORCE_ENV, "")          # empty means unset
    assert REGISTRY.resolve(_req(), pin="pallas_tpu").name == "pallas_tpu"


def test_forcing_never_redirects_residual(monkeypatch):
    """The residual host arm is structural mixed-execution semantics —
    REPRO_BACKEND must not silently change its f32 numerics."""
    monkeypatch.setenv(FORCE_ENV, "xla_ref")
    req = _req(k=17, dtype="bf16", segment=RESIDUAL)
    assert REGISTRY.resolve(req).name == "host_residual"


def test_forcing_never_redirects_structural_main(monkeypatch):
    """forceable=False marks a capacity-based fallback: the pin holds and
    REPRO_BACKEND cannot push it onto the accelerator."""
    monkeypatch.setenv(FORCE_ENV, "pallas_tpu")
    req = _req(forceable=False)
    assert REGISTRY.resolve(req, pin="xla_ref").name == "xla_ref"
    assert REGISTRY.resolve(_req(), pin="xla_ref").name == "pallas_tpu"


def test_fallback_plan_entries_exempt_from_forcing(monkeypatch):
    """An offload=False entry keeps the reference path — and really runs
    it — even under REPRO_BACKEND=pallas_tpu, so ledger fallback
    accounting matches what executed."""
    monkeypatch.setenv(FORCE_ENV, "pallas_tpu")
    eng = OffloadEngine(vmem_budget_kb=1, burst=32)     # nothing fits
    e = eng.plan_entry(512, 512, 16, quantized=False)
    assert not e.offload and e.backend == "xla_ref"
    # prove execution honors the structural pin: pallas must not be built
    calls = []
    pallas = REGISTRY.get("pallas_tpu")
    monkeypatch.setattr(pallas, "build",
                        lambda req: calls.append(req) or (lambda x, w: x))
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 512)) * 0.1
    y = eng.linear(x, w, name="fallback")
    assert not calls
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w.T),
                               rtol=2e-2, atol=2e-2)
    assert eng.stats.fallback_calls == 1
    assert eng.stats.by_backend == {"xla_ref": 1}


def test_register_new_backend_round_trip():
    class Fake:
        name = "cgla_sim"
        def supports(self, req):
            return True
        def auto(self, req):
            return False                        # never volunteers
        def build(self, req):
            return lambda x, w: jnp.zeros((x.shape[0], req.n), jnp.float32)
        def cost_hints(self, req):
            return {"flops": req.flops}

    reg = BackendRegistry()
    reg.register(Fake())
    assert reg.names() == ("cgla_sim",)
    assert reg.resolve(_req(), pin="cgla_sim").name == "cgla_sim"
    out = reg.dispatch(_req(n=8), pin="cgla_sim")(jnp.ones((4, 64)), None)
    assert out.shape == (4, 8)


def test_cost_hints_present():
    req = _req()
    for name in REGISTRY.names():
        hints = REGISTRY.get(name).cost_hints(req)
        assert hints["flops"] == req.flops
        assert "unit" in hints


# ---------------------------------------------------------------------------
# Numerical parity: xla_ref vs pallas_tpu (interpret off-TPU)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k,burst", [
    (8, 512, 384, 128),       # q8_matvec decode path (whisper d_model)
    (4, 1536, 384, 64),       # q8_matvec, skinny M
    (32, 256, 160, 32),       # q8_matmul prefill path
    (64, 384, 1536, 256),     # q8_matmul, whisper ffn.down
])
def test_parity_q8(m, n, k, burst):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + n + k))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    wq = quantize_q8_0(jax.random.normal(kw, (n, k)) * 0.1)
    with REGISTRY.force("pallas_tpu"):
        a = executor.matmul(x, wq, burst=burst, interpret=True)
    with REGISTRY.force("xla_ref"):
        b = executor.matmul(x, wq, burst=burst)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(b, ref.q8_matmul_ref(x, wq),
                               rtol=2e-5, atol=2e-5)


def test_parity_q8_matvec_kernel_selected():
    """The decode shapes above really exercise the matvec kernel."""
    assert kernel_for(8, True) == "q8_matvec"
    assert kernel_for(4, True) == "q8_matvec"
    assert kernel_for(32, True) == "q8_matmul"


@pytest.mark.parametrize("m,n,k,burst", [(8, 64, 96, 32), (32, 128, 384, 128)])
def test_parity_dense(m, n, k, burst):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * k))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (n, k)) * 0.1
    with REGISTRY.force("pallas_tpu"):
        a = executor.matmul(x, w, burst=burst, interpret=True)
    with REGISTRY.force("xla_ref"):
        b = executor.matmul(x, w, burst=burst)
    # both run the paper's 16-bit semantics: bf16 operands, f32 accum
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def test_host_residual_whole_problem_parity():
    """host_residual is pinnable as a whole-problem host baseline (the
    paper's CPU-only row; benchmarks/backend_matrix.py relies on this)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 384), jnp.float32)
    wq = quantize_q8_0(jax.random.normal(jax.random.PRNGKey(1), (64, 384)) * 0.1)
    got = executor.matmul(x, wq, burst=128, backend="host_residual")
    np.testing.assert_allclose(got, ref.q8_matmul_ref(x, wq),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Plan pinning (DESIGN.md §12.3)
# ---------------------------------------------------------------------------
def test_plan_entry_records_backend():
    kw = dict(quantized=True, vmem_budget_kb=8 * 1024, default_burst=256,
              tuner=None)
    assert plan_linear("q", 8, 384, 1536, backend="xla_ref", **kw).backend \
        == "xla_ref"
    assert plan_linear("q", 8, 384, 1536, backend="pallas_tpu", **kw).backend \
        == "pallas_tpu"
    # fallback entries always pin the reference path
    e = plan_linear("big", 1024, 1024, 8, quantized=False, vmem_budget_kb=1,
                    default_burst=32, tuner=None, backend="pallas_tpu")
    assert not e.offload and e.backend == "xla_ref"


def test_plan_entry_zero_main_segment_names_host():
    """k < burst: no main segment exists — the entry must attribute the
    whole linear to the host residual arm that actually runs it, not pin
    a phantom main-segment backend (whisper's enc.frontend, k=n_mels=80,
    hits this at the default burst 256)."""
    e = plan_linear("enc.frontend", 8, 80, 384, quantized=False,
                    vmem_budget_kb=8 * 1024, default_burst=256, tuner=None,
                    backend="pallas_tpu")
    assert e.offload and e.k_main == 0 and e.k_res == 80
    assert e.backend == "host_residual"


def test_plan_entry_backend_honors_forcing(monkeypatch):
    monkeypatch.setenv(FORCE_ENV, "xla_ref")
    e = plan_linear("q", 8, 384, 1536, quantized=True,
                    vmem_budget_kb=8 * 1024, default_burst=256, tuner=None,
                    backend="pallas_tpu")
    assert e.backend == "xla_ref"


@pytest.fixture(scope="module")
def whisper_engine():
    cfg = get_smoke_config("whisper-tiny")
    params = M.init_params(jax.random.PRNGKey(0), cfg, 64)
    eng = ServeEngine(cfg, params, max_len=16, quant="q8_0",
                      offload=OffloadEngine(prefer_pallas=False), eos_id=-1)
    return cfg, eng


def test_plan_backend_roundtrips_through_record_plan(whisper_engine):
    cfg, eng = whisper_engine
    mel = jnp.zeros((1, 8, cfg.n_mels), jnp.float32)
    p1 = record_plan(eng.offload, eng._prefill_fn, eng._serve_params, mel)
    p2 = record_plan(eng.offload, eng._prefill_fn, eng._serve_params, mel)
    assert len(p1) > 0
    assert p1.signature() == p2.signature()     # equality includes .backend
    # engine pins xla_ref; zero-main-segment linears (k < burst, e.g. the
    # k=n_mels frontend) attribute to the host arm that actually runs them
    assert all(e.backend == ("host_residual" if e.k_main == 0 else "xla_ref")
               for e in p1)


def test_plan_backend_roundtrips_through_plancache_zero_retraces(
        whisper_engine):
    """PlanEntry.backend survives the PlanCache round-trip and pinning it
    costs zero retraces in ServeEngine steps (the §10 purity contract)."""
    cfg, eng = whisper_engine
    mel = np.zeros((2, 8, cfg.n_mels), np.float32)
    eng.transcribe(mel, max_new=3)
    traces = eng._step_traces
    hits0 = eng._plans.hits
    for plan in eng._plans.plans.values():
        assert len(plan) > 0
        assert all(e.backend == ("host_residual" if e.k_main == 0
                                 else "xla_ref") for e in plan)
    eng.transcribe(mel, max_new=3)              # steady state
    assert eng._step_traces == traces           # zero retraces
    assert eng._plans.hits > hits0              # plans round-tripped
    by_backend = eng.offload.stats.by_backend
    assert set(by_backend) <= {"xla_ref", "host_residual"}
    # ledger attribution names exactly the backends the plans recorded
    planned = {e.backend for plan in eng._plans.plans.values() for e in plan}
    assert set(by_backend) == planned and sum(by_backend.values()) > 0
    assert eng.energy_report([])["dispatch"]["by_backend"] == \
        dict(eng.offload.stats.by_backend)


# ---------------------------------------------------------------------------
# Single-probe platform detection (the old ops.py duplication)
# ---------------------------------------------------------------------------
def test_platform_probe_is_centralized():
    """``jax.default_backend()`` is probed in exactly one place under src/
    — backends/platform.py (kernels/ops.py and tuning/ used to duplicate
    it)."""
    offenders = []
    for path in glob.glob(os.path.join(ROOT, "src", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("backends", "platform.py")):
            continue
        with open(path, encoding="utf-8") as f:
            if "default_backend()" in f.read():
                offenders.append(os.path.relpath(path, ROOT))
    assert not offenders, f"platform probes outside the registry: {offenders}"


def test_platform_probe_cached(monkeypatch):
    from repro.backends import platform as plat
    plat.reset_probe_cache()
    assert plat.backend_platform() == jax.default_backend()
    # cached: a spoofed entry is returned as-is until reset
    plat._PROBE["platform"] = "tpu"
    assert plat.on_tpu() and not plat.default_interpret()
    plat.reset_probe_cache()
    assert plat.backend_platform() == jax.default_backend()


def test_pallas_refuses_interpret_on_tpu():
    """On a TPU, interpret=True would run the kernels as XLA emulation
    while the ledger still says pallas_tpu: the backend refuses it, and an
    unset flag resolves to native execution."""
    from repro.backends import PallasTPUBackend
    from repro.backends import platform as plat
    be = PallasTPUBackend()
    plat._PROBE["platform"] = "tpu"
    try:
        with pytest.raises(ValueError, match="interpret=True"):
            be.build(_req(interpret=True))
        assert be.cost_hints(_req())["interpret"] is False
    finally:
        plat.reset_probe_cache()
    if jax.default_backend() != "tpu":
        # off the chip, interpret mode is how the kernels are tested
        assert be.cost_hints(_req(interpret=True))["interpret"] is True
