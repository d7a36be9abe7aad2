"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (CPU), as the TPU-target validation required by the assignment."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas,
    tile_plan,
)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rg_lru.ref import lru_sequential_ref, rglru_scan_ref
from repro.kernels.rg_lru.rg_lru import lru_scan_pallas
from repro.kernels.ssd.ref import ssd_scan_ref, ssd_sequential_ref
from repro.kernels.ssd.ssd import ssd_scan_pallas

RNG = np.random.RandomState(42)


def _flash_case(*shape, blocks=(64, 64)):
    """A case named by its shape, and by its blocks where not 64 × 64
    (``None``: the kernel's own tile plan)."""
    name = "-".join(map(str, shape))
    if blocks != (64, 64):
        name += "-bq{}-bk{}".format(*blocks)
    return pytest.param(*shape, *blocks, id=name)


@pytest.mark.parametrize(
    "B,Sq,Skv,H,KV,D,causal,window,block_q,block_k",
    [
        _flash_case(2, 128, 128, 4, 2, 64, True, None),
        _flash_case(1, 256, 256, 4, 1, 32, True, 48),    # MQA + sliding window
        _flash_case(2, 100, 100, 2, 2, 64, True, None),  # non-multiple -> padding
        _flash_case(1, 64, 192, 4, 4, 64, False, None),  # cross-attention style
        _flash_case(1, 128, 128, 8, 2, 128, True, 32),   # GQA 4:1, small window
        # smollm-360m's grouping: 15 query heads over 5 KV heads of 64
        _flash_case(1, 512, 512, 15, 5, 64, True, None),
        _flash_case(1, 300, 300, 15, 5, 64, True, None, blocks=(None, None)),
        # unequal blocks, both ways: dead, diagonal and interior tiles
        _flash_case(1, 512, 512, 6, 2, 64, True, None, blocks=(128, 256)),
        _flash_case(1, 512, 512, 6, 2, 64, True, None, blocks=(256, 128)),
        # a window across block edges: leading tiles are skipped
        _flash_case(1, 512, 512, 4, 2, 64, True, 100, blocks=(128, 64)),
        _flash_case(1, 256, 256, 4, 2, 32, False, 80),
        _flash_case(1, 256, 64, 2, 1, 32, False, 32),    # queries that see no key
        # non-causal, Skv not a multiple of block_k
        _flash_case(2, 96, 200, 6, 3, 64, False, None, blocks=(32, 64)),
    ],
)
def test_flash_attention_matches_ref(B, Sq, Skv, H, KV, D, causal, window,
                                     block_q, block_k):
    q = RNG.randn(B, Sq, H, D).astype(np.float32)
    k = RNG.randn(B, Skv, KV, D).astype(np.float32)
    v = RNG.randn(B, Skv, KV, D).astype(np.float32)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _live_tiles_brute_force(Sq, Skv, block_q, block_k, causal, window):
    q_pos = np.arange(Sq)[:, None]
    k_pos = np.arange(Skv)[None, :]
    mask = np.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return sum(
        mask[i:i + block_q, j:j + block_k].any()
        for i in range(0, Sq, block_q) for j in range(0, Skv, block_k)
    )


@pytest.mark.parametrize(
    "Sq,Skv,causal,window,block_q,block_k",
    [
        (2048, 2048, True, None, None, None),
        (512, 512, True, None, 128, 256),
        (512, 512, True, None, 256, 128),
        (300, 300, True, None, 64, 64),
        (512, 512, True, 100, 128, 64),
        (4096, 4096, True, 1024, None, None),
        (256, 256, False, 80, 64, 64),
        (256, 64, False, 32, 64, 64),
        (96, 200, False, None, 32, 64),
        (200, 96, True, None, 64, 32),
    ],
)
def test_tile_plan_counts_live_tiles(Sq, Skv, causal, window, block_q, block_k):
    """Live tiles are exactly those with an unmasked query/key pair."""
    B, H, KV = 2, 6, 2
    plan = tile_plan(B, Sq, Skv, H, KV, 64, causal, window, block_q, block_k)
    n_q, n_k = -(-Sq // plan.block_q), -(-Skv // plan.block_k)
    assert plan.grid == (B, KV, n_q, n_k)
    want = _live_tiles_brute_force(Sq, Skv, plan.block_q, plan.block_k,
                                   causal, window)
    assert plan.live_tiles == B * KV * want


def test_tile_plan_folds_query_groups_at_smollm_shape():
    """smollm-360m training: one grid step per KV head, 512 × 1024
    blocks, 240 of the 320 steps live under the causal mask."""
    plan = tile_plan(8, 2048, 2048, 15, 5, 64, causal=True)
    assert plan == (512, 1024, (8, 5, 4, 2), 240)
    # a short prefill bucket keeps a single block
    assert tile_plan(1, 64, 64, 15, 5, 64).grid == (1, 5, 1, 1)


@pytest.mark.parametrize(
    "H,KV,D,block_q,block_k",
    [
        (15, 5, 64, 512, 1024),    # smollm-360m
        (48, 8, 128, 256, 1024),   # mixtral-8x22b
        (96, 8, 128, 128, 1024),   # mistral-large-123b
        (16, 16, 64, 512, 1024),   # whisper-medium
        (16, 1, 256, 32, 1024),    # recurrentgemma-9b: wide heads, fewer rows
    ],
)
def test_tile_plan_rows_shrink_with_head_width(H, KV, D, block_q, block_k):
    """A step's query rows times their lane-padded width stay within what
    1536 rows of a head up to 128 wide take."""
    plan = tile_plan(1, 4096, 4096, H, KV, D)
    assert (plan.block_q, plan.block_k) == (block_q, block_k)
    assert H // KV * block_q * max(D, 128) <= 1536 * 128


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


def test_flash_attention_bf16_rounds_output_once():
    """With bf16 inputs the output is the exact attention rounded once to
    bf16: ``p·v`` loses none of the float32 probabilities' precision."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(2 * rng.randn(1, 512, 6, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 512, 2, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 512, 2, 64), jnp.bfloat16)
    exact = np.asarray(attention_ref(
        *(x.astype(jnp.float32) for x in (q, k, v))))
    out = np.asarray(flash_attention_pallas(
        q, k, v, block_q=128, block_k=128, interpret=True), np.float32)
    once = np.asarray(jnp.asarray(exact, jnp.bfloat16), np.float32)
    # Probabilities rounded to bf16 before p·v give 1.24 here.
    assert np.abs(out - exact).mean() <= 1.02 * np.abs(once - exact).mean()


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
