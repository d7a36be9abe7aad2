"""Pallas TPU flash attention (GQA + causal + sliding-window).

TPU adaptation of the flash-attention algorithm (DESIGN.md §2): the KV
loop is the innermost *sequential grid dimension* so the MXU streams
(query block × key block) tiles from VMEM while online-softmax statistics
(m, l) and the output accumulator persist in VMEM scratch across KV steps
— the TPU-native replacement for the GPU's shared-memory tiling.

The grid is ``(B, KV, n_q, n_k)``: one step takes the ``G = H // KV``
query heads that share a KV head as a single ``(G·block_q, D)`` operand
against one ``(block_k, D)`` K/V block, so each K/V block is fetched once
per group, not once per query head.  :func:`tile_plan` derives the block
sizes from the shape and counts the *live* tiles: those with at least one
query/key pair that the causal mask, the sliding window and the padded
tail leave unmasked.  A dead tile computes nothing, and its K/V index map
is clamped to the nearest live block, so the pipeline re-uses the block
it holds and issues no copy.  Only tiles that straddle the diagonal, the
window's edge or the padded tail build the iota mask.

The MXU takes q, k and v in their own dtype with float32 accumulation.
The float32 probabilities reach ``p·v`` as two halves in v's dtype,
``p_hi + p_lo``, so the kernel rounds its output once, as a float32
kernel would, at two narrow passes instead of a multi-pass float32
matmul.  The softmax statistics, the accumulator and the scale stay
float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["TilePlan", "flash_attention_pallas", "tile_plan"]

NEG_INF = -1e30
# Block sizes are rounded to the bfloat16 sublane tile, so a group's query
# blocks stack into one operand without a relayout.
_SUBLANE = 16
_LANES = 128
# Default blocks are the largest powers of two within these limits: the G
# heads' query rows of a step, and the float32 score tile (rows × keys).
# The q, out and accumulator blocks hold rows × D, padded to whole lanes,
# so heads wider than a lane get proportionally fewer rows.  The kernel
# runs in the 16 MiB of scoped VMEM a TPU v5e gives by default: at the
# limits it takes 14.5 MiB at D = 64 and 15.25 MiB at D = 128, and 2048
# rows of 256-wide heads would take 16.75 MiB even at 512 keys.  Fewer,
# larger steps ran faster on a TPU v5e at smollm-360m's training shape
# (PERF.md, section 6).
_MAX_BLOCK_Q, _MAX_BLOCK_K = 512, 1024
_MAX_ROWS = 1536
_MAX_TILE = _MAX_ROWS * _MAX_BLOCK_K


class TilePlan(NamedTuple):
    block_q: int
    block_k: int
    grid: tuple  # (B, KV, n_q, n_k)
    live_tiles: int  # grid steps that compute; the rest are skipped


def _round_up(x, m):
    return -(-x // m) * m


def _pow2_floor(x):
    return 1 << (x.bit_length() - 1)


def _kv_span(iq, *, block_q, block_k, sq, skv, n_k, causal, window, xp):
    """First and last KV block that query block ``iq`` attends to; the
    span is empty when first > last.  ``xp`` is ``np`` for static block
    indices and ``jnp`` for grid indices."""
    q_lo = iq * block_q
    first, last = 0, n_k - 1
    if causal:  # the block's last real query sees keys up to itself
        last = xp.minimum((xp.minimum(q_lo + block_q, sq) - 1) // block_k, n_k - 1)
    if window is not None:  # its first query sees keys from here on
        start = q_lo - window + 1
        first = xp.where(start >= skv, n_k, xp.maximum(start, 0) // block_k)
    return first, last


def tile_plan(B, Sq, Skv, H, KV, D, causal=True, window=None,
              block_q=None, block_k=None):
    """The kernel's tiling for q ``(B, Sq, H, D)`` and k/v ``(B, Skv, KV,
    D)``: block sizes (``block_q``/``block_k`` override the defaults),
    the grid, and how many of its steps are live."""
    G = H // KV
    if block_q is None:
        rows = _MAX_ROWS * _LANES // _round_up(D, _LANES)
        block_q = _pow2_floor(min(max(rows // G, _SUBLANE), _MAX_BLOCK_Q))
    block_q = min(block_q, _round_up(Sq, _SUBLANE))
    if block_k is None:
        block_k = _pow2_floor(min(max(_MAX_TILE // (G * block_q), 128), _MAX_BLOCK_K))
    block_k = min(block_k, _round_up(Skv, _SUBLANE))
    n_q, n_k = pl.cdiv(Sq, block_q), pl.cdiv(Skv, block_k)
    first, last = _kv_span(
        np.arange(n_q), block_q=block_q, block_k=block_k, sq=Sq, skv=Skv,
        n_k=n_k, causal=causal, window=window, xp=np,
    )
    live = np.broadcast_to(np.maximum(last - first + 1, 0), (n_q,))
    per_row = int(live.sum())
    return TilePlan(block_q, block_k, (B, KV, n_q, n_k), B * KV * per_row)


def _needs_mask(iq, ik, *, block_q, block_k, sq, skv, causal, window):
    """Whether tile (iq, ik) masks any real query's key: it straddles the
    diagonal, the window's edge or the padded tail of the keys."""
    q_lo, k_lo = iq * block_q, ik * block_k
    edge = False
    if skv % block_k:
        edge = edge | (k_lo + block_k > skv)
    if causal:
        edge = edge | (k_lo + block_k - 1 > q_lo)
    if window is not None:
        edge = edge | (k_lo < jnp.minimum(q_lo + block_q, sq) - window)
    return edge


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, n_k, block_q, block_k, sq, skv, causal, window):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    G, D = q_ref.shape[1], q_ref.shape[3]
    rows = G * block_q

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        q = q_ref[0].reshape(rows, D)  # the group's heads, stacked
        k = k_ref[0, 0]  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G·bq, bk)
        if masked:
            shape = (G, block_q, block_k)
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
            q_pos, k_pos = q_pos.reshape(s.shape), k_pos.reshape(s.shape)
            mask = k_pos < skv
            if causal:
                mask &= k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (G·bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:  # a row with every key masked so far has m_new = NEG_INF
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)

        def p_dot_v(p_part):
            return jax.lax.dot_general(
                p_part, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        p_hi = p.astype(v.dtype)
        pv = p_dot_v(p_hi)
        if p_hi.dtype != p.dtype:  # what rounding p to v's dtype lost
            pv += p_dot_v((p - p_hi.astype(p.dtype)).astype(v.dtype))
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    geometry = dict(block_q=block_q, block_k=block_k, sq=sq, skv=skv,
                    causal=causal, window=window)
    first, last = _kv_span(iq, n_k=n_k, xp=jnp, **geometry)
    live = (ik >= first) & (ik <= last)
    edge = _needs_mask(iq, ik, **geometry)
    if edge is False:
        pl.when(live)(lambda: step(False))
    else:
        pl.when(live & edge)(lambda: step(True))
        pl.when(live & ~edge)(lambda: step(False))

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).reshape(G, block_q, D).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           block_q=None, block_k=None, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D) -> (B, Sq, H, D).

    ``block_q``/``block_k`` override :func:`tile_plan`'s block sizes."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    plan = tile_plan(B, Sq, Skv, H, KV, D, causal, window, block_q, block_k)
    bq, bk = plan.block_q, plan.block_k
    n_q, n_k = plan.grid[2:]

    # pad sequence dims to block multiples (masked off in-kernel)
    pad_q = n_q * bq - Sq
    pad_k = n_k * bk - Skv
    qt = jnp.moveaxis(q, 2, 1)  # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)  # (B, KV, Skv, D)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    geometry = dict(block_q=bq, block_k=bk, sq=Sq, skv=Skv, causal=causal,
                    window=window)

    def kv_index(b, h, iq, ik):  # dead tiles keep the nearest live block
        first, last = _kv_span(iq, n_k=n_k, xp=jnp, **geometry)
        return b, h, jnp.minimum(jnp.maximum(ik, first), last), 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, n_k=n_k, **geometry),
        grid=plan.grid,
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_index),
            pl.BlockSpec((1, 1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    if pad_q:
        out = out[:, :, :Sq, :]
    return jnp.moveaxis(out, 1, 2)
