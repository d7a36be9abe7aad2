"""Ring-collective kernel suite (interpret-mode Pallas, DESIGN.md §7).

Pins the three-way contract behind the pallas transport:

* the interpret-mode Pallas kernels (grid-emulated ring, one program per
  rank) against the stacked NumPy oracles — bitwise, including float
  payloads, because oracle and kernel share the accumulation order;
* the SPMD ppermute references (what the transport stages under
  vmap/shard_map on non-TPU backends) against the same oracles — so the
  reference *is* the interpret-mode execution of the kernel schedule.

Selectable as the CI interpret-mode leg via ``-m pallas``.
"""
import jax
import numpy as np
import pytest

from repro.kernels.collectives import (
    ring_allgather_stacked,
    ring_allreduce_stacked,
    ring_alltoall_stacked,
    ring_reduce_scatter_stacked,
)
from repro.core.errors import KampingError
from repro.kernels.collectives import ref
from repro.kernels.collectives.collectives import (
    device_ring_allgather,
    device_ring_reduce_scatter,
)

PS = (1, 2, 4, 8)

pytestmark = [pytest.mark.pallas, pytest.mark.parametrize("p", PS)]


def data(p, shape, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed + p)
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.randint(-50, 50, size=(p,) + shape).astype(dtype)
    return rng.randn(p, *shape).astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_kernel_allgather_matches_oracle(p, dtype):
    xs = data(p, (3, 2), dtype)
    out = ring_allgather_stacked(xs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), ref.allgather_stacked_ref(xs)
    )


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_kernel_reduce_scatter_matches_oracle_bitwise(p, dtype):
    """Float payloads included: kernel and oracle share the ring
    accumulation order, so equality is bitwise, not allclose."""
    xs = data(p, (p, 5), dtype, seed=1)
    out = ring_reduce_scatter_stacked(xs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), ref.reduce_scatter_stacked_ref(xs)
    )


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_kernel_allreduce_matches_oracle_bitwise(p, dtype):
    xs = data(p, (3, 7), dtype, seed=2)
    out = ring_allreduce_stacked(xs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), ref.allreduce_stacked_ref(xs)
    )


def test_kernel_alltoall_matches_oracle(p):
    xs = data(p, (p, 2, 3), seed=3)
    out = ring_alltoall_stacked(xs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), ref.alltoall_stacked_ref(xs)
    )


@pytest.mark.parametrize("kernel", [device_ring_allgather,
                                    device_ring_reduce_scatter])
def test_device_ring_kernels_refuse_wide_elements(p, kernel):
    """The TPU ring kernels move 8-, 16- and 32-bit elements: a wider
    payload is refused at trace time, not handed to another path."""
    with pytest.raises(KampingError, match="8-, 16- or 32-bit"):
        kernel(np.zeros((p, 4), np.complex64), "x", p)


def test_kernel_allreduce_uneven_payload(p):
    """Payload size not divisible by p exercises the pad/unpad of the
    reduce-scatter + allgather composition."""
    xs = data(p, (5,), seed=4)  # 5 elements, p in {1,2,4,8}
    out = ring_allreduce_stacked(xs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), ref.allreduce_stacked_ref(xs)
    )


# -- SPMD ppermute references vs the same oracles ---------------------------
def spmd(f, *arrs):
    return jax.vmap(f, axis_name="x")(*arrs)


def test_spmd_ref_allgather_matches_oracle(p):
    xs = data(p, (4, 2), seed=5)
    out = spmd(lambda v: ref.ring_allgather(v, "x", p), xs)
    np.testing.assert_array_equal(
        np.asarray(out), ref.allgather_stacked_ref(xs)
    )


def test_spmd_ref_reduce_scatter_matches_oracle_bitwise(p):
    xs = data(p, (p, 6), seed=6)
    out = spmd(lambda v: ref.ring_reduce_scatter(v, "x", p), xs)
    np.testing.assert_array_equal(
        np.asarray(out), ref.reduce_scatter_stacked_ref(xs)
    )


def test_spmd_ref_allreduce_matches_oracle_bitwise(p):
    xs = data(p, (3, 3), seed=7)
    out = spmd(lambda v: ref.ring_allreduce(v, "x", p), xs)
    np.testing.assert_array_equal(
        np.asarray(out), ref.allreduce_stacked_ref(xs)
    )


def test_spmd_ref_alltoall_matches_oracle(p):
    xs = data(p, (p, 3), seed=8)
    out = spmd(lambda v: ref.ring_alltoall(v, "x", p), xs)
    np.testing.assert_array_equal(
        np.asarray(out), ref.alltoall_stacked_ref(xs)
    )
