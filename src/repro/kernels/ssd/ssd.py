"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation (DESIGN.md §2): the chunk index is a *sequential grid
dimension*; the inter-chunk state (H, N, P) persists in VMEM scratch
across chunk steps, so HBM traffic is exactly one read of (x, log-decay, B, C)
and one write of y per token — the chunk-local quadratic products
(C·Bᵀ masked by the decay kernel) run on the MXU as (Q×N)·(N×Q) and
(Q×Q)·(Q×P) tiles with Q = 128 (lane-aligned).

Grid: (B, H, n_chunks) — heads are independent, so (B, H) parallel axes;
per-(b, h) state is (N, P): mamba2-370m -> 128×64 fp32 = 32 KiB scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _kernel(x_ref, la_ref, lar_ref, b_ref, c_ref, o_ref, state_scr, *, Q,
            n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    la = la_ref[0, 0]  # (Q, 1) cumulative log decay within the chunk
    la_row = lar_ref[0, 0]  # (1, Q) the same, laid out along lanes
    bm = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)  # (Q, N)

    # intra-chunk: w[i,j] = (C_i·B_j) * exp(la_i - la_j) * causal
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    seg = la - la_row  # (Q, Q) = la_i - la_j
    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    w = jnp.where(jq <= iq, cb * jnp.exp(seg), 0.0)
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # inter-chunk contribution from the carried state
    y += jnp.exp(la) * jax.lax.dot_general(
        cm, state_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: S = exp(la_last) * S + sum_j exp(la_last - la_j) B_j x_j^T
    la_last = la_row[:, Q - 1:]  # (1, 1)
    tail = jnp.exp(la_last - la)  # (Q, 1)
    new_contrib = jax.lax.dot_general(
        bm * tail, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, P)
    state_scr[...] = state_scr[...] * jnp.exp(la_last) + new_contrib

    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, a, Bm, C, *, chunk=128, interpret=False):
    """x: (B,S,H,P); a: (B,S,H); Bm/C: (B,S,G,N) -> y: (B,S,H,P).

    G groups are expanded to H in the BlockSpec index maps (h // (H//G)),
    never materialized.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} % chunk {Q} != 0"
    nc = S // Q

    xt = jnp.moveaxis(x, 2, 1)  # (B, H, S, P)
    # Cumulative log decay within each chunk, in XLA: Mosaic has no cumsum.
    log_a = jnp.log(jnp.maximum(jnp.moveaxis(a, 2, 1), 1e-37))
    la = jnp.cumsum(
        log_a.astype(jnp.float32).reshape(Bsz, H, nc, Q), axis=-1
    ).reshape(Bsz, H, S)
    bt = jnp.moveaxis(Bm, 2, 1)  # (B, G, S, N)
    ct = jnp.moveaxis(C, 2, 1)

    grid = (Bsz, H, nc)
    out = pl.pallas_call(
        functools.partial(_kernel, Q=Q, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, ic: (b, h // rep, ic, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, ic: (b, h // rep, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, ic: (b, h, ic, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xt, la[..., None], la[:, :, None, :], bt, ct)
    return jnp.moveaxis(out, 1, 2)
