"""Quickstart: the paper's technique in 60 lines.

  PYTHONPATH=src python examples/quickstart.py

1. Quantize a weight matrix to GGML Q8_0 (blocks of 32 + fp16 scale).
2. Run the mixed-execution dot product: burst-aligned main segment on the
   Pallas TPU kernel (native on a TPU, interpret mode on CPU), residual on
   the host path.
3. Ask the offload dispatcher whether the invocation fits the local-memory
   budget (the paper's LMM-coverage test) and account PDP.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy
from repro.core.offload import OffloadEngine
from repro.core.qformats import quantize_q8_0, reconstruction_error
from repro.kernels import ops


def main():
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)

    # Whisper-tiny's FFN down-projection shape: W (384, 1536), x (tokens, 1536)
    w = jax.random.normal(kw, (384, 1536)) * 0.02
    x = jax.random.normal(kx, (8, 1536))

    # 1) Q8_0 quantization (paper §3.2 / §4.2)
    wq = quantize_q8_0(w)
    err = reconstruction_error(w, wq)
    print(f"Q8_0: {wq.qs.shape[0]}x{wq.k} int8 + {wq.scales.size} fp16 "
          f"scales | MAE {err['mae']:.2e} (paper: 1.39e-4) | "
          f"{wq.nbytes()} bytes vs {w.size*2} fp16 bytes")

    # 2) mixed execution: aligned main on the kernel, residual on host
    y = ops.matmul(x, wq, burst=128, prefer_pallas=True)
    y_ref = x @ w.T
    print(f"mixed-exec matmul: out {y.shape}, max|err| vs dense "
          f"{float(jnp.max(jnp.abs(y - y_ref))):.2e}")

    # 3) offload dispatch + PDP accounting (paper Eq. 1-2)
    eng = OffloadEngine(vmem_budget_kb=32, burst=128, prefer_pallas=True)
    y2 = eng.linear(x, wq, name="ffn.down")
    print(f"dispatcher: offloaded={eng.stats.offloaded_calls} "
          f"fallback={eng.stats.fallback_calls} "
          f"(budget test: activation {x.size*2}B vs 32KB)")
    pdp = energy.pdp_mixed(t_active_s=0.8, t_main_s=1.0,
                           p_accel_w=energy.P_IMAX_LANE_Q8_W * 2)
    print(f"PDP for a 1s step, 0.8s accelerator-active: {pdp:.3f} J "
          f"(Eq. 2; host remainder at {energy.P_ARM_A72_W} W)")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5)
    print("ok")


if __name__ == "__main__":
    main()
