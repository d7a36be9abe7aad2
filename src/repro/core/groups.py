"""Process groups: static ``comm.split`` machinery + grouped lowerings.

KaMPIng's communicator is not just ``MPI_COMM_WORLD``: sub-communicators
created with ``comm.split(color, key)`` are part of the paper's
abstraction stack, and everything built on a communicator (the op-spec
table, capacity policies, transports, request pools) composes over them
unchanged.  This module is the JAX realization (DESIGN.md §9):

* **Groups are static.**  ``MPI_Comm_split`` takes each rank's color at
  runtime; under XLA the group structure must exist at trace time so
  that membership lowers to ``axis_index_groups`` (static colors →
  static groups, the paper's zero-overhead rule).  Traced colors raise
  the trace-time analogue of the paper's leveled assertions — a
  :class:`~repro.core.errors.KampingError` naming the offending value.
* **Groups are uniform.**  SPMD programs stage one program for every
  rank, so every group must have the same size (otherwise per-rank
  result *shapes* would differ).  ``MPI_UNDEFINED`` (opting out of the
  split) has no analogue for the same reason.
* **Groups are a property of the communicator, not of any one op.**
  :func:`split_groups` produces a partition of the *global* axis ranks;
  the split communicator carries it, and every transport primitive
  (``all_gather`` / ``all_to_all`` / ``reduce_scatter_sum`` /
  ``allreduce_sum``), every direct collective (``pmax``, ``ppermute``,
  masked-psum broadcast), and the rank/size topology queries consult it.
  No op-spec row knows about groups at all.

Lowering strategy: each grouped primitive first attempts the native
``axis_index_groups`` lowering (the hardware path under ``shard_map`` /
``pmap``) on the payload flattened behind its group dimension — the TPU
compiler takes seconds per MiB to compile a grouped collective over a
deeper operand, and a fraction of a second over a flat one; where the
running JAX lacks a rule — notably the vmap-as-SPMD
test interpreter, and grouped ``psum`` under some shard_map versions —
it falls back to an *emulation* built from full-axis collectives plus
static group reindexing (a gather of the group's rows / a scatter into
the full layout).  The fallback stages more bytes but identical
semantics, so the differential suites exercise grouped ops everywhere.
``ppermute`` needs no fallback: a group-relative permutation maps to a
static global permutation (:func:`local_perm_to_global`).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from .errors import KampingError

__all__ = [
    "Groups",
    "GroupTables",
    "validate_groups",
    "split_groups",
    "survivor_groups",
    "local_perm_to_global",
    "grouped_all_gather",
    "grouped_all_to_all",
    "grouped_psum",
    "grouped_pmax",
    "grouped_pmin",
    "grouped_psum_scatter",
    "grouped_ppermute",
]

# A partition of the global axis ranks: tuple of equally-sized tuples of
# global rank indices, in group-rank order.
Groups = Tuple[Tuple[int, ...], ...]


def _is_traced(value) -> bool:
    """True for jax tracers / arrays — anything without a trace-time int."""
    return isinstance(value, jnp.ndarray) or (
        hasattr(value, "aval") and not isinstance(value, (int, np.integer))
    )


def validate_groups(groups, world: int) -> Groups:
    """Canonicalize and check a group structure against the axis size.

    Groups must partition ``range(world)`` into disjoint, covering,
    equally-sized tuples (the SPMD uniformity rule — see module doc).
    """
    canon: List[Tuple[int, ...]] = []
    seen: set = set()
    for g in groups:
        members = tuple(int(r) for r in g)
        if not members:
            raise KampingError("comm.split: empty group in group structure")
        for r in members:
            if r < 0 or r >= world:
                raise KampingError(
                    f"comm.split: group member {r} outside the axis "
                    f"(world size {world})"
                )
            if r in seen:
                raise KampingError(
                    f"comm.split: rank {r} appears in more than one group"
                )
            seen.add(r)
        canon.append(members)
    if len(seen) != world:
        missing = sorted(set(range(world)) - seen)
        raise KampingError(
            f"comm.split: groups must cover every rank of the axis; "
            f"missing {missing}"
        )
    sizes = {len(g) for g in canon}
    if len(sizes) != 1:
        raise KampingError(
            f"comm.split: all groups must have the same size under SPMD "
            f"(per-rank result shapes are static); got sizes "
            f"{sorted(len(g) for g in canon)}. Choose colors that "
            f"partition the ranks evenly (MPI_UNDEFINED-style opt-out has "
            f"no static-shape analogue)."
        )
    return tuple(canon)


def _normalize_assignment(name: str, value, size: int) -> List[int]:
    """colors/keys: a per-member sequence or a rank->value callable,
    resolved to a static Python list of ints at trace time."""
    if _is_traced(value):
        raise KampingError(
            f"comm.split: traced {name} — group membership must be static "
            f"at trace time so it lowers to axis_index_groups (the paper's "
            f"zero-overhead rule; cf. the trace-time assertion tier in "
            f"DESIGN.md §9). Pass a Python/NumPy sequence or a rank->"
            f"{name[:-1]} callable instead of a traced array."
        )
    if callable(value):
        value = [value(r) for r in range(size)]
    vals = list(value)
    if len(vals) != size:
        raise KampingError(
            f"comm.split: {name} must have one entry per rank of this "
            f"communicator (size {size}); got {len(vals)}"
        )
    out = []
    for v in vals:
        if _is_traced(v):
            raise KampingError(
                f"comm.split: traced value in {name} — see above; group "
                f"membership must be static at trace time"
            )
        out.append(int(v))
    return out


def split_groups(
    parent: Optional[Groups],
    world: int,
    colors,
    keys=None,
) -> Groups:
    """Split a (possibly already split) communicator by color and key.

    ``parent`` is the current group structure (``None`` = the flat
    communicator, one group covering ``range(world)``).  ``colors`` and
    ``keys`` are indexed by the *current communicator's rank* (0..size-1)
    and — being static — apply uniformly to every existing group, the
    SPMD form of "each rank passes its color".  Within a new group,
    members are ordered by ``(key, parent rank)`` — ``key`` reorders
    ranks, ties keep the parent rank order (MPI_Comm_split's stable-sort
    contract).  Splits compose: splitting a split communicator
    partitions within each existing group.
    """
    if parent is None:
        parent = (tuple(range(world)),)
    else:
        parent = validate_groups(parent, world)
    size = len(parent[0])
    colors = _normalize_assignment("colors", colors, size)
    keys = (
        list(range(size))
        if keys is None
        else _normalize_assignment("keys", keys, size)
    )
    out: List[Tuple[int, ...]] = []
    for grp in parent:
        by_color: dict = {}
        for i, member in enumerate(grp):
            by_color.setdefault(colors[i], []).append((keys[i], i, member))
        for color in sorted(by_color):
            ordered = sorted(by_color[color])  # (key, parent-rank) stable
            out.append(tuple(m for _, _, m in ordered))
    return validate_groups(out, world)


def survivor_groups(world: int, survivors: Sequence[int]) -> Groups:
    """Partition of the *parent* axis putting the survivors in group 0.

    The ULFM shrink→split mapping (DESIGN.md §15): after a failure the
    surviving ranks become one ``comm.split`` group of the old axis, so
    drain/replay collectives during recovery run group-scoped over
    exactly the survivors with the ordinary §9 machinery.  The dead
    ranks are chunked into filler groups of the same size (uniformity is
    the SPMD static-shape rule — their staged programs are never read),
    which requires ``len(survivors)`` to divide ``world``: the whole-
    slice failure model, where hosts are retired in units that keep the
    partition uniform (``WorldComm.shrink`` rounds down to the largest
    valid survivor count).
    """
    surv = sorted(int(r) for r in survivors)
    if not surv:
        raise KampingError("survivor_groups: no survivors")
    if len(set(surv)) != len(surv):
        raise KampingError("survivor_groups: duplicate survivor rank")
    for r in surv:
        if r < 0 or r >= world:
            raise KampingError(
                f"survivor_groups: rank {r} outside the axis (world {world})"
            )
    s = len(surv)
    if world % s:
        raise KampingError(
            f"survivor_groups: {s} survivors do not uniformly partition a "
            f"{world}-rank axis (SPMD groups must be equally sized — shrink "
            "retires whole slices; round down to a divisor of the world "
            "size first)"
        )
    dead = [r for r in range(world) if r not in set(surv)]
    colors = [0] * world
    for i, r in enumerate(dead):
        colors[r] = 1 + i // s
    return split_groups(None, world, colors)


class GroupTables:
    """Static per-rank lookup tables derived from a group structure.

    ``group_id[r]`` / ``group_rank[r]`` — which group global rank ``r``
    belongs to and its position inside it; ``members[r]`` — the full
    member list of ``r``'s group, in group-rank order.  All are NumPy
    constants; indexing them with the traced ``lax.axis_index`` is how a
    rank discovers its group-relative topology with nothing staged but
    one constant gather.
    """

    def __init__(self, groups: Groups, world: int):
        groups = validate_groups(groups, world)
        self.groups = groups
        self.world = world
        self.group_size = len(groups[0])
        self.num_groups = len(groups)
        self.group_id = np.zeros((world,), np.int32)
        self.group_rank = np.zeros((world,), np.int32)
        self.members = np.zeros((world, self.group_size), np.int32)
        for gi, grp in enumerate(groups):
            for i, r in enumerate(grp):
                self.group_id[r] = gi
                self.group_rank[r] = i
                self.members[r] = grp

    def as_index_groups(self) -> List[List[int]]:
        return [list(g) for g in self.groups]


# --------------------------------------------------------------------------
# Grouped primitives: native axis_index_groups first, emulation fallback.
# --------------------------------------------------------------------------
def _axis_of(comm):
    if len(comm._axes) != 1:
        raise KampingError(
            "grouped collectives require a single-axis communicator "
            f"(axis_index_groups indexes one named axis); got axes "
            f"{comm._axes!r}"
        )
    return comm._axes[0]


def _my_members(comm, tables: GroupTables):
    """Traced (group_size,) vector of this rank's group members."""
    return jnp.asarray(tables.members)[lax.axis_index(_axis_of(comm))]


def grouped_all_gather(comm, x, *, tiled: bool = True):
    """Group-scoped all_gather: gather ``x`` from this rank's group.

    Native lowering: ``lax.all_gather(..., axis_index_groups=groups)``.
    Fallback (vmap interpreter): full-axis gather + a static-table gather
    of the group's rows.
    """
    t = comm._group_tables()
    ax = _axis_of(comm)
    try:
        out = lax.all_gather(
            x.reshape(-1), ax, axis=0, tiled=True,
            axis_index_groups=t.as_index_groups(),
        ).reshape((t.group_size,) + tuple(x.shape))
        return out.reshape((-1,) + tuple(x.shape[1:])) if tiled else out
    except NotImplementedError:
        full = lax.all_gather(x, ax, tiled=False)
        out = full[_my_members(comm, t)]
        if tiled:
            return out.reshape((-1,) + tuple(x.shape[1:]))
        return out


def grouped_all_to_all(comm, x):
    """Group-scoped dense personalized exchange of ``(g, ...)`` buckets.

    Fallback: scatter the group buckets into a full ``(p, ...)`` layout
    (zeros toward non-members), run the full-axis exchange, and gather
    back the group's rows — 2x wire volume, identical semantics.
    """
    t = comm._group_tables()
    ax = _axis_of(comm)
    g = t.group_size
    if x.shape[0] != g:
        raise KampingError(
            f"grouped all_to_all: send_buf leading dim {x.shape[0]} must "
            f"equal the group size {g}"
        )
    try:
        return lax.all_to_all(
            x.reshape(g, -1), ax, split_axis=0, concat_axis=0, tiled=False,
            axis_index_groups=t.as_index_groups(),
        ).reshape(x.shape)
    except NotImplementedError:
        mem = _my_members(comm, t)
        full = jnp.zeros((t.world,) + tuple(x.shape[1:]), x.dtype)
        full = full.at[mem].set(x)
        exchanged = lax.all_to_all(
            full, ax, split_axis=0, concat_axis=0, tiled=False
        )
        return exchanged[mem]


def _grouped_reduce(comm, x, native, combine):
    t = comm._group_tables()
    try:
        return native(t.as_index_groups())
    except NotImplementedError:
        full = lax.all_gather(x, _axis_of(comm), tiled=False)
        return combine(full[_my_members(comm, t)])


def grouped_psum(comm, x):
    ax = _axis_of(comm)
    return _grouped_reduce(
        comm, x,
        lambda g: lax.psum(x, ax, axis_index_groups=g),
        lambda rows: jnp.sum(rows, axis=0),
    )


def grouped_pmax(comm, x):
    ax = _axis_of(comm)
    return _grouped_reduce(
        comm, x,
        lambda g: lax.pmax(x, ax, axis_index_groups=g),
        lambda rows: jnp.max(rows, axis=0),
    )


def grouped_pmin(comm, x):
    ax = _axis_of(comm)
    return _grouped_reduce(
        comm, x,
        lambda g: lax.pmin(x, ax, axis_index_groups=g),
        lambda rows: jnp.min(rows, axis=0),
    )


def grouped_psum_scatter(comm, x):
    """Group-scoped reduce-scatter (sum) of ``(g, chunk...)`` slots.

    Fallback: grouped psum + extraction of this rank's slot by its
    group-relative index.
    """
    t = comm._group_tables()
    ax = _axis_of(comm)
    if x.shape[0] != t.group_size:
        raise KampingError(
            f"grouped reduce_scatter: leading dim {x.shape[0]} must equal "
            f"the group size {t.group_size}"
        )
    try:
        return lax.psum_scatter(
            x.reshape(-1), ax, scatter_dimension=0, tiled=True,
            axis_index_groups=t.as_index_groups(),
        ).reshape(x.shape[1:])
    except NotImplementedError:
        red = grouped_psum(comm, x)
        my = jnp.asarray(t.group_rank)[lax.axis_index(ax)]
        return lax.dynamic_index_in_dim(red, my, 0, keepdims=False)


def local_perm_to_global(groups: Groups, perm) -> List[Tuple[int, int]]:
    """Map a group-relative permutation to the global static permutation.

    ``perm`` pairs are group-rank indices ``(src, dst)``; the same
    schedule applies inside every group (the SPMD uniformity rule), so
    the global permutation is its union over groups.
    """
    g = len(groups[0])
    out: List[Tuple[int, int]] = []
    for grp in groups:
        for s, d in perm:
            s, d = int(s), int(d)
            if not (0 <= s < g and 0 <= d < g):
                raise KampingError(
                    f"group-relative permutation pair ({s}, {d}) outside "
                    f"the group size {g}"
                )
            out.append((grp[s], grp[d]))
    return out


def grouped_ppermute(comm, x, perm):
    """Group-scoped ``ppermute``: ``perm`` is group-relative.  Always a
    native lowering — the global permutation is static."""
    t = comm._group_tables()
    return lax.ppermute(
        x, _axis_of(comm), local_perm_to_global(t.groups, perm)
    )
