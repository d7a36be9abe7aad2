"""GridCommunicator plugin: 2-D grid all-to-all (paper §V-A).

Routes each message in two hops over a virtual (here: *physical* — the TPU
mesh axes are the grid) 2-D processor grid, reducing the number of startup
messages per rank from ``p-1`` to ``(rows-1) + (cols-1) ≈ 2·(√p-1)`` at the
cost of ~2x communication volume (every element crosses the wire twice).
On a TPU pod this is the torus-native realization of Kalé-style 2-hop
personalized communication: hop 1 travels along one mesh axis, hop 2 along
the other, so both hops are contention-free on ICI.

Requires a communicator over exactly two axes ``(rows, cols)``; global
rank order is row-major (matching ``Communicator`` over the same tuple).

``grid_alltoall`` / ``grid_alltoallv`` are not re-implementations: they
are the *same op-spec rows* as the flat ``alltoall`` / ``alltoallv``,
re-registered with the 2-hop routing kernel as their transport (the
``transport_attr`` spec column).  Parameter collection, capacity
policies, count inference (which therefore also rides the 2-hop route),
assertions, result packing, and the ``i*`` variants all come from the
shared lowering engine.

Relation to process groups (DESIGN.md §9): on a *single* flattened axis
the same 2-hop schedule is re-expressible as two split sub-communicators
— ``comm.split_by(block=cols)`` (the row-local hop) and
``comm.split_by(stride=cols)`` (the column hop) — which is exactly how
the ``hier`` transport's ``all_to_all`` (core/hier.py) stages it.  This
plugin remains the two-*mesh-axis* form, where each hop is
contention-free on its own physical ICI axis.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .errors import KampingError
from .opspec import OP_TABLE, attach_ops
from .plugins import Plugin

__all__ = ["GridCommunicator"]


class GridCommunicator(Plugin):
    def _grid_axes(self):
        axes = self._axes  # provided by Communicator
        if len(axes) != 2:
            raise KampingError(
                "GridCommunicator requires a communicator over exactly two "
                f"mesh axes (rows, cols); got axes {axes!r}. Construct it as "
                "Communicator((row_axis, col_axis)).extend(GridCommunicator)."
            )
        return axes

    # -- the 2-hop routing kernel (the grid specs' transport) ---------------
    def _two_hop(self, x):
        """x: (p, cap, ...) buckets by global dest rank -> same layout, 2 hops.

        Hop 1 (cols axis): deliver to the destination's *column* within my
        row; hop 2 (rows axis): deliver to the destination row.  Net effect
        identical to the flat all_to_all, with 2·(√p) messages.
        """
        rows_ax, cols_ax = self._grid_axes()
        sr, sc = lax.axis_size(rows_ax), lax.axis_size(cols_ax)
        p = sr * sc
        if x.shape[0] != p:
            raise KampingError(
                f"grid all-to-all: send_buf leading dim {x.shape[0]} != p={p}"
            )
        rest = x.shape[1:]
        # (dest_row j1, dest_col j2, cap...) — row-major global rank
        xg = x.reshape((sr, sc) + rest)
        # Hop 1: along cols. Send to column j2 the bundle over all j1.
        h1 = jnp.moveaxis(xg, 1, 0)  # (j2, j1, cap...)
        h1 = lax.all_to_all(h1, cols_ax, split_axis=0, concat_axis=0,
                            tiled=False)
        # h1[k2, j1, ...] = bucket from (my_row, k2) destined to (j1, my_col)
        # Hop 2: along rows. Send to row j1 the bundle over all k2.
        h2 = jnp.moveaxis(h1, 1, 0)  # (j1, k2, cap...)
        h2 = lax.all_to_all(h2, rows_ax, split_axis=0, concat_axis=0,
                            tiled=False)
        # h2[k1, k2, ...] = bucket from global rank (k1, k2) to me.
        return h2.reshape((p,) + rest)


attach_ops(
    GridCommunicator,
    (
        OP_TABLE["alltoall"].renamed(
            "grid_alltoall",
            transport_attr="_two_hop",
            doc="Dense 2-hop all-to-all: send_buf shaped (p, chunk, ...).",
        ),
        OP_TABLE["alltoallv"].renamed(
            "grid_alltoallv",
            transport_attr="_two_hop",
            doc=(
                "2-hop variant of alltoallv: same bucketed (p, cap, ...) "
                "layout, capacity-policy semantics, count inference, and "
                "assertion staging as ``Communicator.alltoallv`` — the "
                "identical op-spec row, routed over the grid transport."
            ),
        ),
    ),
)
