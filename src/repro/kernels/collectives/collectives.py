"""Pallas ring-collective kernels (DESIGN.md §7).

Two tiers, one ring schedule (shared with ``ref.py``):

* **Emulation kernels** (`ring_allgather_pallas`, ...) — one
  ``pallas_call`` over the globally stacked ``(p, ...)`` array with
  ``grid=(p,)``: program r *is* rank r, and the arrival of a neighbor's
  chunk at ring step s is emulated by an async DMA from the stacked HBM
  buffer — the same per-step data movement and accumulation order as the
  multi-chip kernel, minus the interconnect.  These run under
  ``interpret=True`` on CPU (the CI leg) and compile for a single chip.
* **Device kernels** (`device_ring_allgather`, `device_ring_reduce_scatter`)
  — the true multi-chip path: called per device inside ``shard_map`` on a
  TPU mesh, moving chunks with ``make_async_remote_copy`` over ICI.
  They are selected by the pallas transport only when the backend is TPU;
  CPU CI pins their semantics through the shared-schedule emulation
  kernels and SPMD references instead.

Ring schedule contract (shared with ref.py): allgather step s delivers
the chunk of the s-th left neighbor; reduce-scatter chunk j starts at
rank (j+1) % p and accumulates left-fold in source order j+1, ..., j.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import allreduce_chunk as ref_chunk

__all__ = [
    "ring_allgather_pallas",
    "ring_reduce_scatter_pallas",
    "ring_allreduce_pallas",
    "ring_alltoall_pallas",
    "device_ring_allgather",
    "device_ring_reduce_scatter",
]


# --------------------------------------------------------------------------
# Emulation kernels: grid=(p,) over the stacked global array
# --------------------------------------------------------------------------
def _allgather_kernel(x_hbm, out_ref, chunk, sem):
    r = pl.program_id(0)
    p = pl.num_programs(0)

    def step(s, carry):
        src = lax.rem(r - s + p, p)  # ring step s: s-th left neighbor
        cp = pltpu.make_async_copy(x_hbm.at[src], chunk, sem)
        cp.start()
        cp.wait()
        out_ref[0, src] = chunk[:]
        return carry

    lax.fori_loop(0, p, step, 0)


def ring_allgather_pallas(xs, *, interpret=None):
    """xs: (p, m) stacked per-rank rows -> (p, p, m); out[r] is rank r's
    ring all-gather result."""
    xs = jnp.asarray(xs)
    p, m = xs.shape[0], int(math.prod(xs.shape[1:]))
    x2 = xs.reshape(p, m)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        _allgather_kernel,
        grid=(p,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, p, m), lambda r: (r, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((p, p, m), x2.dtype),
        scratch_shapes=[pltpu.VMEM((m,), x2.dtype), pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="ring_allgather",
    )(x2)
    return out.reshape((p, p) + xs.shape[1:])


def _reduce_scatter_kernel(x_hbm, out_ref, chunk, acc, sem):
    r = pl.program_id(0)
    p = pl.num_programs(0)

    def step(k, carry):
        # chunk r starts at rank (r+1) % p; after k hops the partial has
        # accumulated sources (r+1) ... (r+1+k), left fold.
        src = lax.rem(r + 1 + k, p)
        cp = pltpu.make_async_copy(x_hbm.at[src, r], chunk, sem)
        cp.start()
        cp.wait()

        @pl.when(k == 0)
        def _():
            acc[:] = chunk[:]

        @pl.when(k > 0)
        def _():
            acc[:] = acc[:] + chunk[:]

        return carry

    lax.fori_loop(0, p, step, 0)
    out_ref[0] = acc[:]


def ring_reduce_scatter_pallas(xs, *, interpret=None):
    """xs: (p, p, m) — xs[src, j] is src's contribution to rank j; returns
    (p, m): out[r] = ring-order sum of xs[:, r]."""
    xs = jnp.asarray(xs)
    p = xs.shape[0]
    m = int(math.prod(xs.shape[2:]))
    x3 = xs.reshape(p, p, m)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        _reduce_scatter_kernel,
        grid=(p,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, m), lambda r: (r, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((p, m), x3.dtype),
        scratch_shapes=[
            pltpu.VMEM((m,), x3.dtype),
            pltpu.VMEM((m,), x3.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="ring_reduce_scatter",
    )(x3)
    return out.reshape((p,) + xs.shape[2:])


def ring_allreduce_pallas(xs, *, interpret=None):
    """xs: (p, ...) per-rank payloads -> (p, ...): every rank's ring
    allreduce (reduce-scatter + allgather composition, like the SPMD
    lowering)."""
    xs = jnp.asarray(xs)
    p = xs.shape[0]
    shape = xs.shape[1:]
    n = int(math.prod(shape)) if shape else 1
    chunk = ref_chunk(n, p)
    flat = xs.reshape(p, -1)
    blocks = jnp.zeros((p, p * chunk), flat.dtype).at[:, :n].set(flat)
    blocks = blocks.reshape(p, p, chunk)
    reduced = ring_reduce_scatter_pallas(blocks, interpret=interpret)
    gathered = ring_allgather_pallas(reduced, interpret=interpret)
    return gathered.reshape(p, -1)[:, :n].reshape((p,) + shape)


def _alltoall_kernel(x_hbm, out_ref, chunk, sem):
    r = pl.program_id(0)
    p = pl.num_programs(0)

    def step(s, carry):
        src = lax.rem(r - s + p, p)  # offset-s hop: s-th left neighbor
        cp = pltpu.make_async_copy(x_hbm.at[src, r], chunk, sem)
        cp.start()
        cp.wait()
        out_ref[0, src] = chunk[:]
        return carry

    lax.fori_loop(0, p, step, 0)


def ring_alltoall_pallas(xs, *, interpret=None):
    """xs: (p, p, m) buckets by (source, dest) -> (p, p, m) by (dest,
    source), moved with the offset-scheduled ring exchange."""
    xs = jnp.asarray(xs)
    p = xs.shape[0]
    m = int(math.prod(xs.shape[2:]))
    x3 = xs.reshape(p, p, m)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = pl.pallas_call(
        _alltoall_kernel,
        grid=(p,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, p, m), lambda r: (r, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((p, p, m), x3.dtype),
        scratch_shapes=[pltpu.VMEM((m,), x3.dtype), pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="ring_alltoall",
    )(x3)
    return out.reshape((p, p) + xs.shape[2:])


# --------------------------------------------------------------------------
# Device kernels: per-chip RDMA ring (called inside shard_map on TPU)
#
# Payloads stay in HBM (``pl.ANY``): chunks move HBM to HBM over ICI, and
# only the reduce-scatter's additions pass through VMEM, one bounded tile
# at a time, so no payload size is limited by VMEM.  A flat payload is
# laid out as (rows, 128) with rows a multiple of the tile, so every DMA
# moves whole tiles.  Every ring step has its own semaphores and, in the
# reduce-scatter, its own receive slot: no buffer or semaphore is reused
# within a call, so a fast rank can never overwrite a slot its neighbour
# is still sending from, and no per-step handshake is needed.
# --------------------------------------------------------------------------
_LANES = 128
# Sublane multiple that tiles every supported dtype (int8/bool need 32).
_SUBLANES = 32
# Rows per VMEM tile of the reduce-scatter's additions: 1024 x 128 x 4 B
# = 512 KiB per buffer, two buffers.
_TILE_ROWS = 1024


def _ring_layout(m: int):
    """(rows, tile_rows) of the (rows, 128) layout of an m-element chunk."""
    rows = -(-max(m, 1) // _LANES)
    rows = -(-rows // _SUBLANES) * _SUBLANES
    tile = min(rows, _TILE_ROWS)
    return -(-rows // tile) * tile, tile


def _check_payload(name: str, dtype) -> None:
    if jnp.dtype(dtype).itemsize not in (1, 2, 4):
        from repro.core.errors import KampingError

        raise KampingError(
            f"{name}: the TPU ring kernels move 8-, 16- or 32-bit elements; "
            f"got {jnp.dtype(dtype).name}"
        )


def _neighbor_barrier(axis, my_id, p):
    """Block until both ring neighbors reached this point (prevents a fast
    rank's RDMA from landing before a slow neighbor entered the kernel)."""
    barrier = pltpu.get_barrier_semaphore()
    for nbr in (lax.rem(my_id + 1, p), lax.rem(my_id - 1 + p, p)):
        pltpu.semaphore_signal(
            barrier, inc=1, device_id={axis: nbr},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
    pltpu.semaphore_wait(barrier, 2)


def _device_allgather_kernel(axis, p, x_hbm, out_hbm, local_sem, send_sem,
                             recv_sem):
    my_id = lax.axis_index(axis)
    own = pltpu.make_async_copy(x_hbm, out_hbm.at[my_id], local_sem)
    own.start()
    own.wait()
    _neighbor_barrier(axis, my_id, p)
    right = lax.rem(my_id + 1, p)
    for s in range(p - 1):
        src = lax.rem(my_id - s + p, p)  # chunk held after s hops
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_hbm.at[src],
            dst_ref=out_hbm.at[src],
            send_sem=send_sem.at[s],
            recv_sem=recv_sem.at[s],
            device_id={axis: right},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()


def device_ring_allgather(x, axis, p: int, *, collective_id=7):
    """Per-device ring all-gather over ``axis`` — call INSIDE shard_map on
    a TPU mesh.  x: (m,)-flattenable local chunk; returns the (p, ...)
    stacked gather.  CPU CI covers the schedule via the emulation kernel;
    this entry point is the ICI fast path."""
    _check_payload("device_ring_allgather", x.dtype)
    if x.dtype == jnp.bool_:
        out = device_ring_allgather(
            x.astype(jnp.int8), axis, p, collective_id=collective_id
        )
        return out.astype(jnp.bool_)
    shape = x.shape
    flat = x.reshape(-1)
    m = flat.shape[0]
    rows, _ = _ring_layout(m)
    x2 = jnp.pad(flat, (0, rows * _LANES - m)).reshape(rows, _LANES)
    out = pl.pallas_call(
        functools.partial(_device_allgather_kernel, axis, p),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((p, rows, _LANES), flat.dtype),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((p - 1,)),
            pltpu.SemaphoreType.DMA((p - 1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True
        ),
        name="device_ring_allgather",
    )(x2)
    return out.reshape(p, rows * _LANES)[:, :m].reshape((p,) + shape)


def _device_reduce_scatter_kernel(axis, p, tile, x_hbm, out_hbm, buf_hbm,
                                  acc, mine, local_sem, send_sem, recv_sem):
    my_id = lax.axis_index(axis)
    n_tiles = out_hbm.shape[0] // tile

    def add_own(s, chunk, last):
        """buf[s] + x[chunk] -> buf[s] (or the output on the last step),
        staged through VMEM one tile at a time."""

        def body(t, carry):
            rows = pl.ds(pl.multiple_of(t * tile, tile), tile)
            arrived = pltpu.make_async_copy(
                buf_hbm.at[s, rows], acc, local_sem.at[0]
            )
            own = pltpu.make_async_copy(
                x_hbm.at[chunk, rows], mine, local_sem.at[1]
            )
            arrived.start()
            own.start()
            arrived.wait()
            own.wait()
            a, b = acc[...], mine[...]
            if jnp.issubdtype(a.dtype, jnp.integer) and a.dtype.itemsize == 1:
                # Mosaic adds 16- and 32-bit integers only; the 8-bit sum
                # wraps the same way when taken in 32 bits and truncated.
                a, b = a.astype(jnp.int32), b.astype(jnp.int32)
            acc[...] = (a + b).astype(acc.dtype)
            dst = out_hbm.at[rows] if last else buf_hbm.at[s, rows]
            back = pltpu.make_async_copy(acc, dst, local_sem.at[0])
            back.start()
            back.wait()
            return carry

        lax.fori_loop(0, n_tiles, body, 0)

    _neighbor_barrier(axis, my_id, p)
    right = lax.rem(my_id + 1, p)
    # The partial for chunk (my_id - 1) % p starts here: own contribution.
    send_from = x_hbm.at[lax.rem(my_id - 1 + p, p)]
    for s in range(p - 1):
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_from,
            dst_ref=buf_hbm.at[s],
            send_sem=send_sem.at[s],
            recv_sem=recv_sem.at[s],
            device_id={axis: right},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()
        # The arrived partial is for chunk (my_id - 2 - s) % p; add ours.
        add_own(s, lax.rem(my_id - 2 - s + 2 * p, p), last=s == p - 2)
        send_from = buf_hbm.at[s]


def device_ring_reduce_scatter(x, axis, p: int, *, collective_id=8):
    """Per-device streaming ring reduce-scatter (sum) — call INSIDE
    shard_map on a TPU mesh.  x: (p, chunk...) contributions by
    destination; returns this rank's reduced chunk, accumulated in the
    canonical ring order shared with ref.py / the emulation kernel."""
    _check_payload("device_ring_reduce_scatter", x.dtype)
    shape = x.shape[1:]
    flat = x.reshape(p, -1)
    m = flat.shape[1]
    rows, tile = _ring_layout(m)
    x3 = jnp.pad(flat, ((0, 0), (0, rows * _LANES - m))).reshape(
        p, rows, _LANES
    )
    out, _ = pl.pallas_call(
        functools.partial(_device_reduce_scatter_kernel, axis, p, tile),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, _LANES), flat.dtype),
            # one receive slot per ring step
            jax.ShapeDtypeStruct((p - 1, rows, _LANES), flat.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((tile, _LANES), flat.dtype),
            pltpu.VMEM((tile, _LANES), flat.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((p - 1,)),
            pltpu.SemaphoreType.DMA((p - 1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True
        ),
        name="device_ring_reduce_scatter",
    )(x3)
    return out.reshape(-1)[:m].reshape(shape)
