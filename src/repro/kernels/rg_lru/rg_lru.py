"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

The recurrence h_t = a_t ⊙ h_{t-1} + b_t is elementwise over the channel
dim (VPU work, 8×128 vregs) and sequential over time.  TPU adaptation:
time is blocked into the sequential grid dimension with the carry h in
VMEM scratch; within a block a log-depth Blelloch-style doubling pass
turns the recurrence into O(log T) vectorized passes over the VMEM-resident
(T, C) block — no HBM round-trips inside a block, one (T, C) read + write
per block overall (the memory-roofline optimum for this op).

Grid: (B, n_channel_blocks, n_time_blocks), time innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lru_scan_pallas"]


def _kernel(a_ref, b_ref, o_ref, h_scr, *, T):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # (T, C)
    b = b_ref[0].astype(jnp.float32)

    # inclusive scan of the affine maps via doubling:
    # (A, B) composed with shift-by-k of itself
    A, Bv = a, b
    k = 1
    while k < T:
        A_shift = jnp.concatenate(
            [jnp.ones((k, A.shape[1]), jnp.float32), A[:-k]], axis=0
        )
        B_shift = jnp.concatenate(
            [jnp.zeros((k, Bv.shape[1]), jnp.float32), Bv[:-k]], axis=0
        )
        # compose: f_new(h) = f_cur(f_shift(h)) => A' = A*Ashift, B' = A*Bshift + B
        Bv = A * B_shift + Bv
        A = A * A_shift
        k *= 2
    # apply to the carried h from previous time blocks
    h = A * h_scr[...] + Bv  # (T, C)
    h_scr[...] = h[-1:, :]
    o_ref[0] = h.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_c", "interpret")
)
def lru_scan_pallas(a, b, *, block_t=256, block_c=512, interpret=False):
    """a, b: (B, S, C) fp32 -> h: (B, S, C) (h_0 = 0 prior)."""
    Bsz, S, C = a.shape
    block_t = min(block_t, S)
    block_c = min(block_c, C)
    assert S % block_t == 0, f"S={S} % block_t={block_t}"
    assert C % block_c == 0, f"C={C} % block_c={block_c}"
    nt, ncb = S // block_t, C // block_c

    grid = (Bsz, ncb, nt)  # time innermost => sequential carry
    out = pl.pallas_call(
        functools.partial(_kernel, T=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda bb, ic, it: (bb, it, ic)),
            pl.BlockSpec((1, block_t, block_c), lambda bb, ic, it: (bb, it, ic)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_t, block_c), lambda bb, ic, it: (bb, it, ic)
        ),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
        name="rg_lru_scan",
    )(a, b)
    return out
