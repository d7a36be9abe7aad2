"""The KaMPIng-style Communicator, mapped onto JAX SPMD collectives.

A :class:`Communicator` names one (or a tuple of) mesh axes and provides
collective operations *inside* a ``jax.shard_map`` region.  Calls take
named parameters (:mod:`repro.core.params`); any omitted parameter is
inferred — with zero staged overhead when the information is available at
trace time, and with exactly the communication a hand-rolled implementation
would stage otherwise (paper §III-A: "only required code paths are
generated at compile time", with trace time playing the role of compile
time).

Every collective is one row of the declarative op-spec table
(:mod:`repro.core.opspec`): the spec names the parameter interface and
count-inference rules, a small ``lower`` function stages the data
movement, and the shared engine provides parameter collection, the
static/traced count paths, capacity policies, leveled assertions, result
packing, and the auto-generated non-blocking ``i*`` variants.  Plugins
(grid/sparse) extend the same table — see DESIGN.md §3.

Variable collectives (``*v``) use *capacity policies* in place of the
paper's resize policies because XLA shapes are static: buffers are
fixed-capacity, counts are (possibly traced) element counts.  See
``params.ResizePolicy``.
"""
from __future__ import annotations

import builtins
import functools
import operator
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import groups as _groups
from .compression import get_codec
from .errors import KampingError
from .opspec import OpSpec, Lowering, attach_ops, is_static, static_int
from .params import ParamKind as K
from .result import Result
from .transports import get_transport, resolve_transport

__all__ = ["Communicator", "CORE_SPECS"]


# --------------------------------------------------------------------------
# STL-functor -> hardware-collective mapping (paper §II "reduction via
# lambda" + Boost.MPI functor mapping).
# --------------------------------------------------------------------------
_SUM_FNS = {operator.add, jnp.add, builtins.sum, "sum", "+", "plus"}
_MAX_FNS = {builtins.max, jnp.maximum, "max"}
_MIN_FNS = {builtins.min, jnp.minimum, "min"}
_AND_FNS = {operator.and_, jnp.logical_and, "and", "land"}
_OR_FNS = {operator.or_, jnp.logical_or, "or", "lor"}


def _try_hash_lookup(fn, table) -> bool:
    try:
        return fn in table
    except TypeError:  # unhashable
        return False


class Communicator:
    """Collective operations over one or more mesh axes.

    Instantiate *inside* a shard_map-ed function::

        def step(x):
            comm = Communicator("data")
            return comm.allreduce(send_buf(x), op(operator.add))

    The collective methods (``allgather`` ... ``scatterv``) and their
    non-blocking ``i*`` variants are generated from ``CORE_SPECS`` at
    class-creation time — see :func:`repro.core.opspec.attach_ops`.

    ``transport`` selects the default collective backend for every op on
    this communicator (``"xla"`` | ``"pallas"`` | any registered name,
    DESIGN.md §7); a per-call ``transport(...)`` parameter overrides it::

        comm = Communicator("data", transport="pallas")   # ring kernels
        comm.allgather(send_buf(x), transport("xla"))     # per-call
    """

    def __init__(self, axis: Any = "data", transport: Optional[str] = None,
                 groups=None, compression: Optional[str] = None,
                 deterministic: Optional[str] = None, plan=None):
        self.axis = axis
        self._axes: Tuple = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
        # Default collective backend for every op on this communicator
        # (DESIGN.md §7); a per-call transport(...) parameter overrides it.
        # Validated eagerly so a typo is a construction-time error.
        if transport is not None:
            get_transport(transport)
        self.transport_name = transport
        # Default payload codec for every *sum reduction* on this
        # communicator (DESIGN.md §10); a per-call compression(...)
        # parameter overrides it (compression(None) disables it).  A
        # default codec silently skips integer payloads.  Stateless —
        # error feedback needs the per-call parameter's state channel.
        if compression is not None:
            get_codec(compression)
        self.compression_name = compression
        # Default deterministic reduction schedule for every reduction on
        # this communicator (DESIGN.md §12); a per-call deterministic(...)
        # parameter overrides it (deterministic(None) disables it).  The
        # default carries no leaf count — each rank's payload is one leaf.
        if deterministic is not None and deterministic not in ("tree",):
            raise KampingError(
                f"Communicator(deterministic={deterministic!r}): the only "
                "registered scheme is 'tree' (or None)"
            )
        self.deterministic_name = deterministic
        # Default cost-model plan for every op on this communicator
        # (DESIGN.md §13): "auto" (fitted cost model picks the cheapest
        # measured transport per call) or a planner.Plan with an explicit
        # transport override.  A plan only speaks when no per-call
        # transport(...) parameter and no communicator transport default
        # is set — explicit choices always win.  A per-call plan(...)
        # parameter overrides it (plan(None) disables it).
        if plan is not None:
            from .planner import Plan as _Plan

            if plan != "auto" and not isinstance(plan, _Plan):
                raise KampingError(
                    f"Communicator(plan={plan!r}): expected None, 'auto', "
                    "or a repro.core.Plan instance"
                )
        self.plan = plan
        # Group scope (DESIGN.md §9): None = the flat communicator; else a
        # static partition of the axis ranks (tuple of equally-sized
        # tuples of global ranks).  Normally produced by split()/
        # split_by(); validated lazily because the axis size is only
        # known in trace context.
        self.groups = (
            None if groups is None else tuple(tuple(int(r) for r in g)
                                              for g in groups)
        )
        self._gt_cache = None

    # -- topology ----------------------------------------------------------
    def _group_tables(self) -> "_groups.GroupTables":
        """Static lookup tables of this communicator's group structure
        (requires trace context for the axis size; cached)."""
        if self.groups is None:
            raise KampingError("flat communicator has no group tables")
        if self._gt_cache is None:
            self._gt_cache = _groups.GroupTables(
                self.groups, self.world_size()
            )
        return self._gt_cache

    def world_size(self) -> int:
        """Size of the underlying mesh axis (or axes product) — the split
        communicator's parent world (cf. MPI_COMM_WORLD's size)."""
        n = 1
        for a in self._axes:
            n *= lax.axis_size(a)
        return n

    def size(self) -> int:
        """Communicator size. Static at trace time (cf. MPI_Comm_size).
        For a split communicator this is the *group* size."""
        if self.groups is not None:
            return self._group_tables().group_size
        return self.world_size()

    def global_rank(self):
        """This rank's index on the underlying mesh axis (traced)."""
        return lax.axis_index(self.axis if len(self._axes) > 1 else self._axes[0])

    def rank(self):
        """This rank's index (traced value; cf. MPI_Comm_rank).  For a
        split communicator: the group-relative rank."""
        if self.groups is not None:
            t = self._group_tables()
            return jnp.asarray(t.group_rank)[self.global_rank()]
        return self.global_rank()

    def group_id(self):
        """Index of this rank's group (traced; 0 for a flat communicator)."""
        if self.groups is None:
            return jnp.zeros((), jnp.int32)
        return jnp.asarray(self._group_tables().group_id)[self.global_rank()]

    @property
    def num_groups(self) -> int:
        """Number of groups (static; 1 for a flat communicator)."""
        return 1 if self.groups is None else len(self.groups)

    # -- process groups (comm.split; DESIGN.md §9) --------------------------
    def _with_groups(self, new_groups) -> "Communicator":
        """Clone (class, plugin state, transport default) with a new group
        structure."""
        comm = type(self).__new__(type(self))
        comm.__dict__.update(self.__dict__)
        comm.groups = new_groups
        comm._gt_cache = None
        return comm

    def split(self, color, key=None) -> "Communicator":
        """Partition this communicator by color (cf. ``MPI_Comm_split``).

        ``color`` assigns each rank of *this* communicator to a group:
        a sequence of length ``size()`` (indexed by this communicator's
        rank) or a rank->color callable.  ``key`` (same indexing)
        reorders ranks within a group — members are ordered by ``(key,
        rank)``, ties keeping rank order (MPI's stable-sort contract).

        Colors must be **static** (Python/NumPy values): static colors
        become static groups at trace time, so membership lowers to
        ``axis_index_groups`` with nothing staged — the paper's
        zero-overhead rule.  Traced colors raise a trace-time
        :class:`KampingError` (the static analogue of a leveled
        assertion).  Groups must be equally sized (SPMD result shapes
        are static; there is no ``MPI_UNDEFINED`` opt-out).

        The returned communicator is fully group-scoped: ``rank()`` /
        ``size()`` are group-relative, and *every* op-spec row —
        including ``*v`` capacity policies, count inference, and the
        ``i*`` variants — as well as every transport backend operates
        within the group.  Splits compose: splitting a split
        communicator partitions within each existing group.
        """
        if len(self._axes) != 1:
            raise KampingError(
                "comm.split requires a single-axis communicator (group "
                f"membership indexes one named axis); got axes "
                f"{self._axes!r}. A two-axis grid communicator is "
                "re-expressible as two splits of the flattened axis — "
                "see DESIGN.md §9."
            )
        new_groups = _groups.split_groups(
            self.groups, self.world_size(), color, key
        )
        return self._with_groups(new_groups)

    def split_by(self, *, block: Optional[int] = None,
                 stride: Optional[int] = None) -> "Communicator":
        """Structured split shorthands.

        ``split_by(block=g)`` — contiguous blocks of ``g`` ranks (color =
        ``rank // g``): the intra-node/intra-group communicator of a
        hierarchical scheme.  ``split_by(stride=g)`` — ranks with equal
        ``rank % g`` (color = ``rank % g``): the cross-group "peer"
        communicator connecting equal positions of every block.  Exactly
        one of the two must be given; it must divide ``size()``.
        """
        if (block is None) == (stride is None):
            raise KampingError(
                "comm.split_by: pass exactly one of block=... or stride=..."
            )
        p = self.size()
        g = int(block if block is not None else stride)
        if g <= 0 or p % g:
            raise KampingError(
                f"comm.split_by: {'block' if block is not None else 'stride'}"
                f"={g} must be a positive divisor of the communicator size "
                f"{p}"
            )
        if block is not None:
            return self.split([r // g for r in range(p)])
        return self.split([r % g for r in range(p)])

    # -- plugin support (paper §III-F) --------------------------------------
    def extend(self, *plugin_classes):
        """Return a communicator extended with plugin mixins.

        Plugins may override collectives and add new named parameters —
        the mechanism KaMPIng uses for grid/sparse all-to-all, ULFM, and
        reproducible reduce.  Plugin collectives are rows of the same
        op-spec table as the core ones.
        """
        bases = tuple(plugin_classes) + (type(self),)
        cls = type("+".join(c.__name__ for c in bases), bases, {})
        ext = cls.__new__(cls)
        ext.__dict__.update(self.__dict__)
        for p in plugin_classes:
            init = getattr(p, "install", None)
            if init is not None:
                init(ext)
        return ext

    # -- group-aware primitive helpers --------------------------------------
    # The scalar collectives every lowering shares: flat communicators use
    # the plain lax ops; split communicators route through the grouped
    # lowerings (native axis_index_groups with an interpreter fallback —
    # core/groups.py, DESIGN.md §9).
    def _psum(self, x):
        if self.groups is not None:
            return _groups.grouped_psum(self, x)
        return lax.psum(x, self.axis)

    def _pmax(self, x):
        if self.groups is not None:
            return _groups.grouped_pmax(self, x)
        return lax.pmax(x, self.axis)

    def _pmin(self, x):
        if self.groups is not None:
            return _groups.grouped_pmin(self, x)
        return lax.pmin(x, self.axis)

    def _ppermute(self, x, perm):
        """ppermute with communicator-relative ``perm``: group-relative
        pairs map to one static global permutation on a split
        communicator."""
        if self.groups is not None:
            return _groups.grouped_ppermute(self, x, perm)
        return lax.ppermute(x, self.axis, perm)

    # -- transports ---------------------------------------------------------
    def _dense_alltoall(self, x):
        """One dense (flat, single-hop) all_to_all over the communicator's
        axis or axes — rank order is row-major over the axis tuple.  On a
        split communicator: the group-scoped exchange of ``(g, ...)``
        buckets."""
        if self.groups is not None:
            return _groups.grouped_all_to_all(self, x)
        ax = self._axes[0] if len(self._axes) == 1 else self._axes
        return lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=True)

    # -- reduction kernel ----------------------------------------------------
    def _reduce_impl(self, x, op_param, transport=None, codec=None,
                     codec_state=None, codec_explicit=True,
                     deterministic=None, det_leaves=None, codec_scale=None):
        t = transport if transport is not None else resolve_transport(self)
        fn = op_param.value
        x = jnp.asarray(x)
        if deterministic is not None:
            # Deterministic path (DESIGN.md §12): the canonical tree is
            # pure ppermute — it bypasses the transport's reduction
            # primitives entirely, so the schedule (and the bits) are
            # transport-invariant by construction, including hier.
            from .reproducible import deterministic_reduce

            if codec is not None:
                if _try_hash_lookup(fn, _SUM_FNS):
                    # Quantized-leaf semantics: encode once, tree-
                    # accumulate the quantized partials exactly.
                    return codec.deterministic_allreduce_sum(
                        self, x, codec_state, leaves=det_leaves,
                        scale=codec_scale,
                    )
                if codec_explicit:
                    raise KampingError(
                        f"compression('{codec.name}') requires a sum "
                        f"reduction (op(operator.add)); got op={fn!r}. "
                        "Drop the compression parameter for "
                        "min/max/logical/lambda reductions."
                    )
                return (
                    self._reduce_impl(
                        x, op_param, transport=t,
                        deterministic=deterministic, det_leaves=det_leaves,
                    ),
                    codec_state,
                )
            # Functor mapping onto a binary tree combiner.  The and/or
            # functors keep the non-deterministic lowering's int32
            # min/max semantics so the two paths agree bitwise.
            if _try_hash_lookup(fn, _SUM_FNS):
                tree_fn = jnp.add
            elif _try_hash_lookup(fn, _MAX_FNS):
                tree_fn = jnp.maximum
            elif _try_hash_lookup(fn, _MIN_FNS):
                tree_fn = jnp.minimum
            elif _try_hash_lookup(fn, _AND_FNS):
                out = deterministic_reduce(
                    self, x.astype(jnp.int32), jnp.minimum,
                    leaves=det_leaves,
                )
                return out.astype(x.dtype)
            elif _try_hash_lookup(fn, _OR_FNS):
                out = deterministic_reduce(
                    self, x.astype(jnp.int32), jnp.maximum,
                    leaves=det_leaves,
                )
                return out.astype(x.dtype)
            else:
                tree_fn = fn  # deterministic_reduce raises if not callable
            return deterministic_reduce(self, x, tree_fn, leaves=det_leaves)
        if codec is not None:
            # Compressed path (DESIGN.md §10): a codec encodes a *sum*
            # payload — non-sum functors have no exact quantized
            # accumulator.  An explicit compression(...) parameter is a
            # loud trace-time error; a communicator *default* codec
            # silently skips non-sum reductions (it only claims sum
            # payloads — the same rule as integer payloads), keeping the
            # (value, state) caller contract with the state unchanged.
            if _try_hash_lookup(fn, _SUM_FNS):
                return codec.allreduce_sum(self, t, x, codec_state,
                                           scale=codec_scale)
            if codec_explicit:
                raise KampingError(
                    f"compression('{codec.name}') requires a sum reduction "
                    f"(op(operator.add)); got op={fn!r}. Drop the "
                    "compression parameter for min/max/logical/lambda "
                    "reductions."
                )
            return (
                self._reduce_impl(x, op_param, transport=t), codec_state
            )
        if _try_hash_lookup(fn, _SUM_FNS):
            return t.allreduce_sum(self, x)
        # Non-sum well-known functors stay on the XLA scalar collectives
        # under every transport: pmax/pmin are latency-bound and have no
        # ring-bandwidth advantage, and keeping one lowering makes them
        # bitwise transport-invariant by construction.
        if _try_hash_lookup(fn, _MAX_FNS):
            return self._pmax(x)
        if _try_hash_lookup(fn, _MIN_FNS):
            return self._pmin(x)
        if _try_hash_lookup(fn, _AND_FNS):
            return self._pmin(x.astype(jnp.int32)).astype(x.dtype)
        if _try_hash_lookup(fn, _OR_FNS):
            return self._pmax(x.astype(jnp.int32)).astype(x.dtype)
        # Reduction via lambda: left fold in rank order (deterministic,
        # supports non-commutative ops). Staged as gather + lax.scan; the
        # gather is pure data movement, so the result is bitwise identical
        # whichever transport moved it.
        if not callable(fn):
            raise KampingError(
                f"kamping.op: {fn!r} is neither a recognized functor name "
                "(operator.add, jnp.maximum, 'sum', 'max', ...) nor "
                "callable; pass an STL-style functor, a jnp ufunc, or a "
                "binary lambda"
            )
        gathered = t.all_gather(self, x, tiled=False)

        def body(acc, v):
            return fn(acc, v), None

        acc, _ = lax.scan(body, gathered[0], gathered[1:])
        return acc

    # -- rooted value distribution -------------------------------------------
    def _bcast_value(self, x, r):
        from .serialization import Serialized, deserialize_like

        if isinstance(x, Serialized):
            payload = self._bcast_value(x.buffer, r)
            return deserialize_like(x, payload)
        x = jnp.asarray(x)
        if (
            isinstance(r, (int, np.integer))
            and len(self._axes) == 1
            and self.groups is None
            and jax.default_backend() == "tpu"
        ):
            # Static root -> the hardware-optimized CollectiveBroadcast HLO.
            # (No CPU lowering exists, so the interpret/dry-run environment
            # takes the masked-psum path below — semantically identical.
            # Split communicators always mask: root is group-relative.)
            return lax.pbroadcast(x, self._axes[0], int(r))
        # Traced root / multi-axis / split: masked (grouped) psum — rank()
        # is group-relative, so the same root index selects each group's
        # own root and every group broadcasts independently.
        mask = self.rank() == r
        if x.dtype == jnp.bool_:
            masked = jnp.where(mask, x, False)
            return self._pmax(masked.astype(jnp.int32)).astype(jnp.bool_)
        return self._psum(x * mask.astype(x.dtype))

    # -- conveniences over the generated surface ------------------------------
    def allreduce_single(self, *args):
        """Scalar allreduce (used by the paper's BFS termination check)."""
        out = self.allreduce(*args)
        return out if not isinstance(out, Result) else out.recv_buf


# --------------------------------------------------------------------------
# Lowerings: the data movement of each op, one small function per row.
# Everything else (packs, counts, policies, assertions, results, i*) is
# the engine.
# --------------------------------------------------------------------------
def _lower_allgather(low: Lowering):
    if low.has(K.SEND_RECV_BUF):
        # Simplified MPI_IN_PLACE (paper §III-G): buffer holds one slot
        # per rank, this rank's slot at index `rank`.
        x = low.value(K.SEND_RECV_BUF)
        p = low.p
        if x.shape[0] != p:
            raise KampingError(
                f"kamping.{low.spec.name}(send_recv_buf): leading dim "
                f"{x.shape[0]} != communicator size {p}"
            )
        mine = lax.dynamic_index_in_dim(x, low.rank(), 0, keepdims=False)
        out = low.all_gather(mine, tiled=False)
        return out.reshape(x.shape)
    return low.all_gather(low.value(K.SEND_BUF))


def _lower_gatherv(low: Lowering):
    """Shared allgatherv/gatherv lowering: three count regimes.

    * static uniform ``send_count`` (default: capacity) — exact concat,
      inferred counts/displs are compile-time constants, nothing staged;
    * static per-rank ``recv_counts`` (numpy array) — the true
      variable-count path: exact *ragged* concatenation with exclusive
      prefix displacements, still nothing staged;
    * traced ``send_count`` — padded layout (rank i's data at
      displacement ``i*cap``); the counts gather is staged only when
      ``recv_counts_out()`` asked for it (paper Fig. 2's exchange).
    """
    x = low.value(K.SEND_BUF)
    cap, p = x.shape[0], low.p
    n = low.value(K.SEND_COUNT, cap)

    rc_param = low.pack.get(K.RECV_COUNTS)
    rc_in = rc_param.value if (rc_param is not None and not rc_param.is_out) else None
    if rc_in is not None and is_static(rc_in):
        counts = np.asarray(rc_in, np.int64).reshape(-1)
        if counts.shape[0] != p:
            raise KampingError(
                f"kamping.{low.spec.name}: recv_counts must have one entry "
                f"per rank (p={p}); got {counts.shape[0]}"
            )
        if (counts < 0).any() or (counts > cap).any():
            raise KampingError(
                f"kamping.{low.spec.name}: static recv_counts must lie in "
                f"[0, capacity={cap}]; got {counts.tolist()}"
            )
        if low.has(K.SEND_COUNT):
            n_static = static_int(n)
            if n_static is None:
                raise KampingError(
                    f"kamping.{low.spec.name}: traced send_count cannot be "
                    f"combined with static recv_counts (the exact ragged "
                    f"path is resolved at trace time); drop send_count or "
                    f"supply it statically"
                )
            if (counts > n_static).any():
                # MPI: recvcounts[i] must match sender i's declared count;
                # exceeding it would deliver data beyond the valid prefix.
                raise KampingError(
                    f"kamping.{low.spec.name}: recv_counts "
                    f"{counts.tolist()} exceed send_count({n_static}) — "
                    f"data beyond the sender's declared valid prefix"
                )
        total = int(counts.sum())
        if total:
            # Gather only up to the largest count — counts are static, so
            # the slice is trace-time and the wire volume is max(counts),
            # not the full capacity.
            g = low.all_gather(x[: int(counts.max())], tiled=False)
            buf = jnp.concatenate(
                [g[i, : int(c)] for i, c in enumerate(counts) if c], axis=0
            )
        else:
            buf = x[:0]
        displs = np.concatenate([[0], np.cumsum(counts)[:-1]])
        low.emit("recv_counts", lambda: jnp.asarray(counts, jnp.int32))
        low.emit("recv_displs", lambda: jnp.asarray(displs, jnp.int32))
        return buf

    n_static = static_int(n)
    if n_static is not None:
        # Zero-overhead path: counts known at trace time -> exact concat,
        # inferred counts/displs are compile-time constants.
        buf = low.all_gather(x[:n_static])
        low.emit("recv_counts", lambda: jnp.full((p,), n_static, jnp.int32))
        low.emit(
            "recv_displs", lambda: jnp.arange(p, dtype=jnp.int32) * n_static
        )
        return buf

    buf = low.all_gather(x)  # padded layout
    low.emit(
        "recv_counts",
        lambda: low.all_gather(jnp.asarray(n, jnp.int32), tiled=False),
    )
    low.emit("recv_displs", lambda: jnp.arange(p, dtype=jnp.int32) * cap)
    return buf


def _lower_gather(low: Lowering):
    return low.all_gather(low.value(K.SEND_BUF))


def _lower_alltoall(low: Lowering):
    x = low.value(K.SEND_BUF)
    p = low.p
    if x.shape[0] != p:
        raise KampingError(
            f"kamping.{low.spec.name}: send_buf leading dim {x.shape[0]} "
            f"must equal communicator size {p}"
        )
    return low.alltoall(x)


def _lower_alltoallv(low: Lowering):
    x = low.value(K.SEND_BUF)
    buf = low.alltoall(x)
    low.emit(
        "recv_displs",
        lambda: jnp.arange(low.p, dtype=jnp.int32) * buf.shape[1],
    )
    low.emit(
        "send_displs",
        lambda: jnp.arange(low.p, dtype=jnp.int32) * x.shape[1],
    )
    if low.value(K.SEND_COUNTS) is not None:  # supplied, not *_out()
        def _recv_counts():
            sc = low.value(K.SEND_COUNTS)
            if is_static(sc):
                # Zero-overhead inference: a static send_counts vector is
                # the same trace-time constant on every rank (SPMD stages
                # one program), so rank j's count toward me is sc[rank] —
                # a local constant gather, *no* staged transpose.
                scv = jnp.asarray(np.asarray(sc).reshape(-1), jnp.int32)
                return jnp.broadcast_to(scv[low.rank()], (low.p,))
            # Traced counts: the staged transpose (the paper's
            # default-parameter communication), riding the op's own
            # transport/route.
            return low.counts_transpose(sc)

        low.emit("recv_counts", _recv_counts)
    return buf


def _lower_allreduce(low: Lowering):
    x = low.value(K.SEND_BUF, low.value(K.SEND_RECV_BUF))
    return low.reduce(x, low.pack[K.OP])


def _lower_reduce_scatter(low: Lowering):
    """MPI_Reduce_scatter_block: send_buf (p, chunk, ...) — slot j is this
    rank's contribution to rank j; each rank receives the op-reduction of
    its slot over all ranks.  Sum on a single axis lowers to the
    hardware ``reduce-scatter`` HLO (lax.psum_scatter); other functors
    fall back to reduce + block extraction."""
    x = jnp.asarray(low.value(K.SEND_BUF, low.value(K.SEND_RECV_BUF)))
    p = low.p
    if x.ndim < 1 or x.shape[0] != p:
        raise KampingError(
            f"kamping.{low.spec.name}: send_buf leading dim "
            f"{x.shape[0] if x.ndim else 0} must equal communicator size {p} "
            f"(slot j holds this rank's contribution to rank j)"
        )
    comm = low.comm
    fn = low.pack[K.OP].value
    if _try_hash_lookup(fn, _SUM_FNS):
        return low.reduce_scatter_sum(x)
    red = low.reduce(x, low.pack[K.OP])
    return lax.dynamic_index_in_dim(red, comm.rank(), 0, keepdims=False)


def _lower_scan(low: Lowering, inclusive: bool):
    x = jnp.asarray(low.value(K.SEND_BUF))
    fn = low.pack[K.OP].value
    gathered = low.all_gather(x, tiled=False)
    if _try_hash_lookup(fn, _SUM_FNS):
        csum = jnp.cumsum(gathered, axis=0)
        pref = (
            csum
            if inclusive
            else jnp.concatenate([jnp.zeros_like(gathered[:1]), csum[:-1]], 0)
        )
    else:
        # True rank-order fold (no identity seed, so non-commutative /
        # non-zero-identity functors follow textbook MPI_Scan semantics;
        # exscan's rank-0 value — undefined in MPI — is zeros).
        def body(acc, v):
            nxt = fn(acc, v)
            return nxt, (nxt if inclusive else acc)

        _, tail = lax.scan(body, gathered[0], gathered[1:])
        head = gathered[:1] if inclusive else jnp.zeros_like(gathered[:1])
        pref = jnp.concatenate([head, tail], 0)
    return lax.dynamic_index_in_dim(pref, low.rank(), 0, keepdims=False)


def _lower_bcast(low: Lowering):
    x = low.value(K.SEND_RECV_BUF)
    r = low.value(K.ROOT, 0)
    return low.comm._bcast_value(x, r)


def _lower_scatter(low: Lowering):
    x = low.value(K.SEND_BUF)
    r = low.value(K.ROOT, 0)
    x = low.comm._bcast_value(x, r)
    return lax.dynamic_index_in_dim(x, low.rank(), 0, keepdims=False)


def _lower_scatterv(low: Lowering):
    """Root's bucketed (p, cap, ...) buffer + per-rank counts; rank i
    receives bucket i (capacity-policy semantics matching alltoallv)."""
    x = low.value(K.SEND_BUF)  # capacity policy already applied
    r = low.value(K.ROOT, 0)
    comm = low.comm
    x = comm._bcast_value(x, r)
    mine = lax.dynamic_index_in_dim(x, comm.rank(), 0, keepdims=False)

    def _recv_count():
        sc = low.value(K.SEND_COUNTS)
        if sc is None:
            raise KampingError(
                f"kamping.{low.spec.name}: recv_count_out() requires "
                f"send_counts(...) to infer from"
            )
        if is_static(sc):
            # Zero-overhead path: static counts are trace-time identical
            # on all ranks (MPI: counts significant only at root), so the
            # lookup is a local gather from a constant — nothing staged.
            scb = jnp.asarray(sc, jnp.int32)
        else:
            scb = comm._bcast_value(jnp.asarray(sc, jnp.int32), r)
        return lax.dynamic_index_in_dim(scb, comm.rank(), 0, keepdims=False)

    low.emit("recv_count", _recv_count)
    return mine


def _lower_barrier(low: Lowering):
    return low.comm._psum(jnp.zeros((), jnp.int32))


def _lower_send_recv(low: Lowering):
    x = low.value(K.SEND_BUF)
    perm = low.kw.get("perm")
    if perm is None:
        if not low.has(K.DEST):
            raise KampingError(
                f"kamping.{low.spec.name}: pass perm=[(src,dst),...] or dest(fn)"
            )
        dfn = low.value(K.DEST)
        p = low.p
        perm = [(i, int(dfn(i)) % p) for i in range(p)]
    # perm is communicator-relative: on a split communicator the pairs
    # are group-rank indices, mapped to one static global permutation.
    return low.ppermute(x, perm)


# --------------------------------------------------------------------------
# The core table.  One row per collective; the surface (blocking methods,
# i* variants, result packing, assertions) is generated from it.
# --------------------------------------------------------------------------
_ALLTOALLV_HINT = (
    "Use with_flattened(...) to build buckets from destination->data "
    "mappings."
)

CORE_SPECS: Tuple[OpSpec, ...] = (
    OpSpec(
        name="allgather",
        lower=_lower_allgather,
        required=((K.SEND_BUF, K.SEND_RECV_BUF),),
        accepted=(K.RECV_BUF,),
        in_place_ignored=(K.SEND_COUNT,),
        doc="MPI_Allgather. Accepts send_buf or send_recv_buf (in-place).",
    ),
    OpSpec(
        name="allgatherv",
        lower=_lower_gatherv,
        required=(K.SEND_BUF,),
        accepted=(K.SEND_COUNT, K.RECV_COUNTS, K.RECV_DISPLS, K.RECV_BUF),
        doc=(
            "MPI_Allgatherv with parameter inference (paper Fig. 1/3).\n\n"
            "``send_buf(x)`` — x has static capacity ``cap = x.shape[0]``;\n"
            "``send_count(n)`` — valid prefix length (default: cap, static);\n"
            "``recv_counts(c)`` / ``recv_counts_out()`` — supplied or "
            "inferred (inference stages one all-gather of the scalar count "
            "— exactly the exchange in paper Fig. 2);\n"
            "``recv_displs(...)`` / ``recv_displs_out()``.\n\n"
            "With static counts the result is the exact concatenation and "
            "*no* extra communication is staged (the zero-overhead path); "
            "a static per-rank ``recv_counts`` array gives the exact "
            "*ragged* concatenation.  With traced counts the result uses "
            "the padded layout: rank i's data at displacement ``i*cap``."
        ),
    ),
    OpSpec(
        name="gather",
        lower=_lower_gather,
        required=(K.SEND_BUF,),
        accepted=(K.ROOT, K.RECV_BUF),
        doc=(
            "MPI_Gather — SPMD note: result materializes on *all* ranks "
            "(an all-gather); `root` kept for API parity."
        ),
    ),
    OpSpec(
        name="gatherv",
        lower=_lower_gatherv,
        required=(K.SEND_BUF,),
        accepted=(
            K.SEND_COUNT, K.RECV_COUNTS, K.RECV_DISPLS, K.RECV_BUF, K.ROOT,
        ),
        doc=(
            "MPI_Gatherv: true variable-count gather. Same count regimes "
            "as allgatherv — in particular a static per-rank "
            "``recv_counts(np.array([...]))`` yields the exact ragged "
            "concatenation with exclusive-prefix displacements, with zero "
            "staged count communication.  SPMD note: the result "
            "materializes on all ranks; ``root`` kept for API parity."
        ),
    ),
    OpSpec(
        name="alltoall",
        lower=_lower_alltoall,
        required=(K.SEND_BUF,),
        accepted=(K.RECV_BUF,),
        doc="MPI_Alltoall: send_buf shaped (p, chunk, ...).",
    ),
    OpSpec(
        name="alltoallv",
        lower=_lower_alltoallv,
        required=(K.SEND_BUF,),
        accepted=(
            K.SEND_COUNTS, K.RECV_COUNTS, K.RECV_DISPLS, K.SEND_DISPLS,
            K.RECV_BUF,
        ),
        bucketed=True,
        bucket_hint=_ALLTOALLV_HINT,
        heavy_count_check=True,
        doc=(
            "MPI_Alltoallv with capacity policies (the MoE-dispatch "
            "workhorse).\n\n"
            "``send_buf(x)`` — bucketed layout ``(p, cap, ...)``: ``x[j]`` "
            "is the (padded) bucket destined for rank ``j``;\n"
            "``send_counts(sc)`` — (p,) valid element counts per "
            "destination (static np arrays take the zero-overhead path);\n"
            "``recv_counts(...)``/``recv_counts_out()`` — supplied, or "
            "inferred with one staged counts all_to_all (paper's "
            "default-parameter communication);\n"
            "``recv_buf(policy)`` — capacity policy for the receive side.\n\n"
            "Returns recv_buf ``(p, cap_r, ...)`` (+ requested outs); entry "
            "``[j]`` is what rank j sent here."
        ),
    ),
    OpSpec(
        name="allreduce",
        lower=_lower_allreduce,
        required=((K.SEND_BUF, K.SEND_RECV_BUF), K.OP),
        accepted=(K.RECV_BUF,),
        compressible=True,
        deterministic=True,
        doc=(
            "MPI_Allreduce with functor mapping / reduction-via-lambda.\n\n"
            "Sum reductions additionally accept ``compression(\"name\")`` "
            "(int8-ef / fp8-e4m3 / topk / registered codecs, DESIGN.md "
            "§10); error-feedback state passed via "
            "``compression(name, state=err)`` comes back as the result's "
            "``compression_state`` field.\n\n"
            "``deterministic(\"tree\", leaves=m)`` (DESIGN.md §12) replaces "
            "the transport's reduction with the canonical perfect-binary-"
            "tree schedule over the global leaf order: send_buf is the "
            "``(m, ...)`` stack of this rank's leaf partials and the result "
            "is bitwise independent of p for fixed global leaf data."
        ),
    ),
    OpSpec(
        name="reduce",
        lower=_lower_allreduce,
        required=((K.SEND_BUF, K.SEND_RECV_BUF), K.OP),
        accepted=(K.ROOT, K.RECV_BUF),
        compressible=True,
        deterministic=True,
        doc=(
            "MPI_Reduce: like allreduce; `root(...)` kept for API parity.\n\n"
            "Under SPMD every rank computes the value (documented deviation: "
            "there is no cheaper root-only reduction on a TPU mesh).  "
            "Accepts ``compression(...)`` and ``deterministic(...)`` like "
            "allreduce."
        ),
    ),
    OpSpec(
        name="reduce_scatter",
        lower=_lower_reduce_scatter,
        required=((K.SEND_BUF, K.SEND_RECV_BUF), K.OP),
        accepted=(K.RECV_BUF,),
        compressible=True,
        deterministic=True,
        doc=(
            "MPI_Reduce_scatter_block: ``send_buf(x)`` with x shaped "
            "``(p, chunk, ...)`` — slot j is this rank's contribution to "
            "rank j; returns the op-reduction of this rank's slot over all "
            "ranks, shaped ``(chunk, ...)``.  ``op(operator.add)`` on a "
            "single axis lowers to the hardware reduce-scatter "
            "(lax.psum_scatter); other functors reduce then extract.\n\n"
            "``deterministic(\"tree\")`` (DESIGN.md §12) evaluates the "
            "canonical cross-rank tree over the full payload and extracts "
            "this rank's slot; ``leaves=`` is rejected here (the (p, "
            "chunk, ...) layout already fixes one leaf per rank)."
        ),
    ),
    OpSpec(
        name="scan",
        lower=functools.partial(_lower_scan, inclusive=True),
        required=(K.SEND_BUF, K.OP),
        doc="MPI_Scan (inclusive prefix) over ranks.",
    ),
    OpSpec(
        name="exscan",
        lower=functools.partial(_lower_scan, inclusive=False),
        required=(K.SEND_BUF, K.OP),
        doc="MPI_Exscan (exclusive prefix) over ranks.",
    ),
    OpSpec(
        name="bcast",
        lower=_lower_bcast,
        required=(K.SEND_RECV_BUF,),
        accepted=(K.ROOT,),
        doc="MPI_Bcast. ``send_recv_buf`` on all ranks; ``root`` defaults 0.",
    ),
    OpSpec(
        name="scatter",
        lower=_lower_scatter,
        required=(K.SEND_BUF,),
        accepted=(K.ROOT,),
        doc=(
            "MPI_Scatter: root's (p, chunk, ...) buffer; each rank gets "
            "[rank]."
        ),
    ),
    OpSpec(
        name="scatterv",
        lower=_lower_scatterv,
        required=(K.SEND_BUF,),
        accepted=(K.ROOT, K.SEND_COUNTS, K.RECV_COUNT, K.RECV_BUF),
        bucketed=True,
        doc=(
            "MPI_Scatterv: root's bucketed ``(p, cap, ...)`` buffer + "
            "per-rank ``send_counts``; rank i receives bucket i "
            "(``(cap_r, ...)``) with capacity-policy semantics matching "
            "alltoallv (``recv_buf(grow_only(c))`` resizes, NORMAL-level "
            "overflow assertion on shrink).  ``recv_count_out()`` returns "
            "this rank's valid element count; ``root`` defaults 0."
        ),
    ),
    OpSpec(
        name="barrier",
        lower=_lower_barrier,
        nonblocking=False,
        doc=(
            "Semantic no-op under SPMD bulk-synchronous execution; stages a "
            "trivial psum so program order is preserved where it matters."
        ),
    ),
    OpSpec(
        name="send_recv",
        lower=_lower_send_recv,
        required=(K.SEND_BUF,),
        accepted=(K.DEST, K.TAG),
        kw_accepted=("perm",),
        doc=(
            "Combined send+recv (SPMD p2p = collective_permute).\n\n"
            "Either pass ``perm=[(src, dst), ...]`` or ``dest(fn)`` where "
            "fn maps rank -> destination rank (a static schedule)."
        ),
    ),
)

attach_ops(Communicator, CORE_SPECS)
