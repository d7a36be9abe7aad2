"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (CPU), as the TPU-target validation required by the assignment."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rg_lru.ref import lru_sequential_ref, rglru_scan_ref
from repro.kernels.rg_lru.rg_lru import lru_scan_pallas
from repro.kernels.ssd.ref import ssd_scan_ref, ssd_sequential_ref
from repro.kernels.ssd.ssd import ssd_scan_pallas

RNG = np.random.RandomState(42)


@pytest.mark.parametrize(
    "B,Sq,Skv,H,KV,D,causal,window",
    [
        (2, 128, 128, 4, 2, 64, True, None),
        (1, 256, 256, 4, 1, 32, True, 48),     # MQA + sliding window
        (2, 100, 100, 2, 2, 64, True, None),   # non-multiple -> padding
        (1, 64, 192, 4, 4, 64, False, None),   # cross-attention style
        (1, 128, 128, 8, 2, 128, True, 32),    # GQA 4:1, small window
    ],
)
def test_flash_attention_matches_ref(B, Sq, Skv, H, KV, D, causal, window):
    q = RNG.randn(B, Sq, H, D).astype(np.float32)
    k = RNG.randn(B, Skv, KV, D).astype(np.float32)
    v = RNG.randn(B, Skv, KV, D).astype(np.float32)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64,
        interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(np.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q = RNG.randn(1, 128, 4, 64).astype(np.float32)
    k = RNG.randn(1, 128, 2, 64).astype(np.float32)
    v = RNG.randn(1, 128, 2, 64).astype(np.float32)
    qd, kd, vd = (jnp.asarray(x, dtype) for x in (q, k, v))
    out = flash_attention_pallas(qd, kd, vd, interpret=True)
    ref = attention_ref(qd, kd, vd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=atol, rtol=atol,
    )


@pytest.mark.parametrize(
    "B,S,H,P,G,N,Q",
    [
        (2, 64, 4, 16, 1, 32, 16),
        (1, 128, 2, 32, 2, 16, 32),
        (1, 64, 8, 8, 1, 8, 64),   # single chunk
        (2, 96, 4, 16, 4, 16, 32),
    ],
)
def test_ssd_kernel_matches_sequential(B, S, H, P, G, N, Q):
    x = RNG.randn(B, S, H, P).astype(np.float32) * 0.5
    a = np.clip(RNG.rand(B, S, H).astype(np.float32), 0.3, 0.99)
    Bm = RNG.randn(B, S, G, N).astype(np.float32) * 0.3
    C = RNG.randn(B, S, G, N).astype(np.float32) * 0.3
    seq = ssd_sequential_ref(x, a, Bm, C)
    chk = ssd_scan_ref(x, a, Bm, C, chunk=Q)
    pls = ssd_scan_pallas(x, a, Bm, C, chunk=Q, interpret=True)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(seq), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(pls), np.asarray(seq), atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize(
    "B,S,C,bt,bc",
    [(2, 64, 32, 16, 32), (1, 128, 64, 32, 32), (1, 32, 128, 32, 64),
     (3, 64, 32, 64, 32)],
)
def test_lru_kernel_matches_sequential(B, S, C, bt, bc):
    a = np.clip(RNG.rand(B, S, C).astype(np.float32), 0.2, 0.999)
    b = RNG.randn(B, S, C).astype(np.float32)
    seq = lru_sequential_ref(a, b)
    asc = rglru_scan_ref(a, b)
    pls = lru_scan_pallas(a, b, block_t=bt, block_c=bc, interpret=True)
    np.testing.assert_allclose(np.asarray(asc), np.asarray(seq), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pls), np.asarray(seq), atol=1e-5, rtol=1e-5)


def test_lru_decay_stability_long_sequence():
    """Long-horizon stability: |h| stays bounded for a in (0,1)."""
    B, S, C = 1, 512, 16
    a = np.full((B, S, C), 0.999, np.float32)
    b = np.ones((B, S, C), np.float32) * 0.01
    out = np.asarray(lru_scan_pallas(a, b, block_t=128, block_c=16, interpret=True))
    assert np.isfinite(out).all()
    assert (np.abs(out) <= 0.01 / (1 - 0.999) + 1e-3).all()


def test_flash_attention_gradient_is_chunked_attentions():
    """The model's differentiable kernel (models.layers.flash_attention):
    the forward is the kernel, the gradient that of chunked_attention."""
    import jax

    from repro.models.layers import chunked_attention, flash_attention

    q = RNG.randn(2, 128, 6, 64).astype(np.float32)
    k = RNG.randn(2, 128, 2, 64).astype(np.float32)
    v = RNG.randn(2, 128, 2, 64).astype(np.float32)
    g = RNG.randn(2, 128, 6, 64).astype(np.float32)

    def kernel_loss(q, k, v):
        return (flash_attention(q, k, v, True, None, 64) * g).sum()

    def xla_loss(q, k, v):
        return (chunked_attention(q, k, v, causal=True, chunk=64) * g).sum()

    np.testing.assert_allclose(
        float(kernel_loss(q, k, v)), float(xla_loss(q, k, v)), rtol=1e-5
    )
    got = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(xla_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
