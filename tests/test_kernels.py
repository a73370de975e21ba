"""Per-kernel validation: Pallas kernels (interpret=True on CPU) swept over
shapes/dtypes and asserted allclose against the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qformats import quantize_q8_0
from repro.kernels import ref
from repro.kernels.bf16_matmul import bf16_matmul
from repro.kernels.q8_matmul import q8_matmul, vmem_claim_bytes
from repro.kernels.q8_matvec import q8_matvec
from repro.kernels import ops


def _w(key, n, k, scale=0.05):
    return jax.random.normal(key, (n, k)) * scale


# ---------------------------------------------------------------------------
# q8_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (8, 64, 64, 8, 64, 32),
    (16, 128, 256, 16, 64, 64),
    (32, 256, 128, 16, 128, 128),
    (128, 256, 512, 64, 128, 256),     # default-ish MXU tiling
    (8, 512, 96, 8, 256, 32),          # skinny K with whole blocks
])
def test_q8_matmul_vs_ref(m, n, k, bm, bn, bk):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * n + k))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    wq = quantize_q8_0(_w(kw, n, k))
    got = q8_matmul(x, wq.flat_qs(), wq.scales, block_m=bm, block_n=bn,
                    block_k=bk, interpret=True)
    want = ref.q8_matmul_ref(x, wq)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_q8_matmul_dtypes(xdtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (16, 64)) * 0.5).astype(xdtype)
    wq = quantize_q8_0(_w(jax.random.PRNGKey(1), 64, 64))
    got = q8_matmul(x, wq.flat_qs(), wq.scales, block_m=8, block_n=64,
                    block_k=32, interpret=True)
    want = ref.q8_matmul_ref(x.astype(jnp.float32), wq)
    tol = 2e-2 if xdtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert got.dtype == jnp.float32


def test_q8_matmul_rejects_partial_blocks():
    x = jnp.ones((16, 64))
    wq = quantize_q8_0(jnp.ones((64, 64)))
    with pytest.raises(ValueError):
        q8_matmul(x, wq.flat_qs(), wq.scales, block_m=8, block_n=64,
                  block_k=48, interpret=True)   # 48 % 32 != 0
    with pytest.raises(ValueError):
        q8_matmul(x[:10], wq.flat_qs(), wq.scales, block_m=8, block_n=64,
                  block_k=32, interpret=True)   # M=10 % 8 != 0


def test_vmem_claim_model():
    """The BlockSpec working set (LMM-sizing analog) is monotone in every
    block dim and matches the documented formula."""
    base = vmem_claim_bytes(128, 256, 256)
    assert vmem_claim_bytes(256, 256, 256) > base
    assert vmem_claim_bytes(128, 512, 256) > base
    assert vmem_claim_bytes(128, 256, 512) > base
    db_x = 2 * 128 * 256 * 2
    db_q = 2 * 256 * 256
    db_s = 2 * 256 * 128 * 4          # (256, 8) f32 band, lanes pad to 128
    deq = 256 * 256 * 4               # f32 dequantized tile
    acc = 128 * 256 * 4 * 2
    assert base == db_x + db_q + db_s + deq + acc
    # the resident scales band grows with the full contraction, one
    # 128-lane tile per 128 Q8_0 blocks
    assert vmem_claim_bytes(128, 256, 256, k=4096) == base
    assert vmem_claim_bytes(128, 256, 256, k=8192) == base + db_s


# ---------------------------------------------------------------------------
# q8_matvec (decode path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,n,k,bn", [
    (8, 128, 64, 64),
    (8, 512, 384, 512),      # whisper d_model
    (16, 1536, 384, 512),    # whisper d_ff x d_model
])
def test_q8_matvec_vs_ref(b, n, k, bn):
    kx, kw = jax.random.split(jax.random.PRNGKey(b + n))
    x = jax.random.normal(kx, (b, k), jnp.float32)
    wq = quantize_q8_0(_w(kw, n, k))
    got = q8_matvec(x, wq.flat_qs(), wq.scales, block_n=bn, interpret=True)
    np.testing.assert_allclose(got, ref.q8_matvec_ref(x, wq),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# bf16_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k", [(8, 64, 64), (32, 128, 384), (64, 256, 512)])
def test_bf16_matmul_vs_ref(m, n, k):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + n + k))
    x = (jax.random.normal(kx, (m, k)) * 0.3).astype(jnp.bfloat16)
    w = (_w(kw, n, k) * 5).astype(jnp.bfloat16)
    got = bf16_matmul(x, w, block_m=8, block_n=64, block_k=64, interpret=True)
    want = ref.matmul_bf16_ref(x, w)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert got.dtype == jnp.float32


# ---------------------------------------------------------------------------
# ops.matmul — the dispatcher the model zoo calls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape_lead", [(), (3,), (2, 5)])
@pytest.mark.parametrize("kk", [64, 96, 130, 383])   # incl. ragged K
def test_ops_matmul_q8_mixed_exec(shape_lead, kk):
    """The public entry point handles leading batch dims and ragged K via
    the paper's main/residual split — allclose to the monolithic oracle."""
    kx, kw = jax.random.split(jax.random.PRNGKey(kk))
    x = jax.random.normal(kx, (*shape_lead, 4, kk), jnp.float32)
    w = _w(kw, 32, kk)
    k_main = (kk // 32) * 32
    wq_full = quantize_q8_0(w[:, :k_main]) if k_main else None
    got = ops.matmul(x, w, burst=32, prefer_pallas=True, interpret=True)
    want = jnp.einsum("...k,nk->...n", x, w)
    # dense path runs the paper's 16-bit kernel (bf16 operands, f32 accum):
    # tolerance is bf16 ulp-scale, not f32
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_ops_matmul_q8_weights():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 384), jnp.float32)
    wq = quantize_q8_0(_w(jax.random.PRNGKey(1), 1536, 384))
    got = ops.matmul(x, wq, burst=128, prefer_pallas=True, interpret=True)
    want = ref.q8_matmul_ref(x, wq)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ops_matmul_pallas_vs_xla_path_agree():
    """prefer_pallas True (interpret) and False (XLA dequant) must agree —
    they share the dequant definition."""
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 256), jnp.float32)
    wq = quantize_q8_0(_w(jax.random.PRNGKey(3), 128, 256))
    a = ops.matmul(x, wq, burst=64, prefer_pallas=True, interpret=True)
    b = ops.matmul(x, wq, burst=64, prefer_pallas=False)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
