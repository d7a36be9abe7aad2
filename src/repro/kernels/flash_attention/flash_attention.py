"""Pallas TPU flash attention (GQA + causal + sliding-window).

TPU adaptation of the flash-attention algorithm (DESIGN.md §2): the KV
loop is the innermost *sequential grid dimension* so the MXU streams
(block_q × block_k) tiles from VMEM while online-softmax statistics
(m, l) and the output accumulator persist in VMEM scratch across KV steps
— the TPU-native replacement for the GPU's shared-memory tiling.  GQA is
handled in the BlockSpec index maps (`h // group` selects the KV head), so
K/V blocks are never physically repeated.

Block sizes default to (128, 128): MXU-aligned (multiples of 128 lanes)
and VMEM-friendly (a q-block of 128×head_dim bf16 plus two kv blocks and
fp32 accumulators stay well under 1 MiB for head_dim ≤ 256).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_q, block_k, n_k, causal, window, seq_kv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_kv
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           block_q=128, block_k=128, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    # pad sequence dims to block multiples (masked off in-kernel)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    qt = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)  # (B, KV, Skv, D)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = qt.shape[2] // block_q
    n_k = kt.shape[2] // block_k

    grid = (B, H, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            block_q=block_q,
            block_k=block_k,
            n_k=n_k,
            causal=causal,
            window=window,
            seq_kv=Skv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    if pad_q:
        out = out[:, :, :Sq, :]
    return jnp.moveaxis(out, 1, 2)
