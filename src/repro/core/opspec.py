"""Declarative collective op specs + the single lowering engine (tentpole).

Every collective in the library is described ONCE by an :class:`OpSpec`:
its named-parameter interface (required / accepted / in-place-ignored
kinds), how receive counts and displacements are inferred, which
assertion tiers it participates in, and a ``lower`` function that stages
*only the data movement*.  One engine — :func:`execute` — implements
everything that used to be hand-rolled per collective in
``communicator.py``:

* trace-time parameter-pack collection and validation,
* the zero-overhead static-count path vs. the traced-count padded path
  (a lowering emits out-fields lazily; nothing is staged unless the
  corresponding ``*_out()`` parameter was requested),
* capacity (resize) policies on bucketed ``(p, cap, ...)`` send buffers,
  with the NORMAL-level overflow assertion,
* the HEAVY-level communication assertion (global sent == received),
* :class:`~repro.core.result.Result` packing in request order,
* auto-generation of the non-blocking ``i*`` variant (paper §III-E).

Specs are attached to a class with :func:`attach_ops`; plugins register
their ops through exactly the same table (paper §III-F), optionally
swapping the *routing* (e.g. the grid communicator reuses the
``alltoallv`` spec verbatim with a 2-hop route).  Orthogonally, every
row accepts the ``transport(...)`` parameter selecting the collective
*backend* (``xla`` HLOs vs. ``pallas`` ring kernels — see
:mod:`repro.core.transports` and DESIGN.md §7), and the reduction rows
additionally accept ``compression(...)`` selecting the *payload codec*
(:mod:`repro.core.compression`, DESIGN.md §10).  ``OP_TABLE`` is
the global registry: "every public collective is defined via the
op-spec table" is a testable property (tests/test_opspec.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import ir
from . import params as kp
from .compression import resolve_codec
from .errors import AssertionLevel, KampingError, check_enabled
from .nonblocking import NonBlockingResult
from .params import ParamKind as K
from .params import collect_params
from .result import make_result
from .transports import resolve_transport

__all__ = [
    "OpSpec", "Lowering", "OP_TABLE", "OP_OWNERS", "attach_ops", "execute",
    "is_static", "static_int",
]


# Method-name -> spec, across the core communicator and every plugin.
OP_TABLE: Dict[str, "OpSpec"] = {}

# Method-name -> owning class name, recorded by attach_ops at registration
# (provenance for tooling, e.g. the API.md generator's core-vs-plugin
# grouping — no name heuristics).
OP_OWNERS: Dict[str, str] = {}

# Out-requestable parameter kinds and the result field each one fills.
_OUT_FIELDS = {
    K.RECV_COUNTS: "recv_counts",
    K.RECV_COUNT: "recv_count",
    K.RECV_DISPLS: "recv_displs",
    K.SEND_COUNTS: "send_counts",
    K.SEND_DISPLS: "send_displs",
}


def is_static(value) -> bool:
    """True when a count-like value is known at trace time."""
    return isinstance(value, (int, np.integer, np.ndarray))


def _payload_nbytes(pack) -> int:
    """Static per-rank payload size of a call's send buffer (0 when no
    buffer or no static shape) — the cost model's interpolation key."""
    p = pack.get(K.SEND_BUF) or pack.get(K.SEND_RECV_BUF)
    if p is None or p.value is None:
        return 0
    try:
        v = jnp.asarray(p.value)
        return int(v.size) * v.dtype.itemsize
    except (TypeError, ValueError):
        return 0


def static_int(value) -> Optional[int]:
    return int(value) if isinstance(value, (int, np.integer)) else None


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One row of the collective table.

    ``lower`` stages the data movement for the op and returns the receive
    buffer; side information (counts, displacements) is *emitted* on the
    :class:`Lowering` as lazily-evaluated thunks so it is only staged
    when the caller requested it.
    """

    name: str
    lower: Callable[["Lowering"], Any]
    required: Tuple = ()
    accepted: Tuple = ()
    in_place_ignored: Tuple = ()
    # (p, cap, ...) bucketed send layout: engine validates the shape and
    # applies the recv_buf capacity policy (+ NORMAL overflow assertion).
    bucketed: bool = False
    bucket_hint: str = ""
    # HEAVY tier: stage the global sent==received check when send_counts
    # are available (costs one counts transpose + two psums).
    heavy_count_check: bool = False
    # Reduction rows additionally accept the engine-level
    # ``compression("name")`` parameter (payload codec, DESIGN.md §10).
    compressible: bool = False
    # Reduction rows also accept the engine-level ``deterministic(...)``
    # parameter (p-invariant canonical-tree schedule, DESIGN.md §12).
    deterministic: bool = False
    # Auto-generate the non-blocking ``i<name>`` variant.
    nonblocking: bool = True
    # Attribute name on the communicator providing the dense-exchange
    # routing; None routes through the resolved transport backend's
    # all_to_all.  Plugins remap this to reuse a spec over a different
    # routing kernel (e.g. the grid 2-hop route); it is an op-level
    # override and wins over the per-call/per-communicator transport.
    transport_attr: Optional[str] = None
    # Python keyword arguments the generated method accepts (everything
    # else is a trace-time TypeError, like a hand-written signature).
    kw_accepted: Tuple[str, ...] = ()
    doc: str = ""

    def renamed(self, name: str, *, transport_attr=None, doc=None) -> "OpSpec":
        """A plugin-facing copy of this spec under a new method name."""
        return dataclasses.replace(
            self,
            name=name,
            transport_attr=transport_attr or self.transport_attr,
            doc=doc or self.doc,
        )


class Lowering:
    """Per-call context handed to a spec's ``lower``.

    Exposes the collected parameter pack, topology, transport-aware
    collective helpers, and the out-field emit machinery.
    """

    def __init__(self, comm, spec: OpSpec, pack, kw):
        self.comm = comm
        self.spec = spec
        self.pack = pack
        self.kw = kw
        # Backend resolution (DESIGN.md §7): per-call transport(...) param
        # > communicator default > "xla".  Resolved once, at trace time.
        # A resolved plan (per-call plan(...) param > communicator
        # default, DESIGN.md §13) may pick the transport — but only when
        # neither an explicit transport parameter nor a communicator
        # transport default exists: a plan never overrides an explicit
        # choice.  Transport selection is bitwise-neutral (§7 contract).
        tparam = pack.get(K.TRANSPORT)
        tvalue = tparam.value if tparam is not None else None
        pparam = pack.get(K.PLAN)
        plan_v = (
            pparam.value if pparam is not None else getattr(comm, "plan", None)
        )
        if (
            tvalue is None
            and plan_v is not None
            and getattr(comm, "transport_name", None) is None
            and spec.transport_attr is None
        ):
            from .planner import plan_call_transport

            tvalue = plan_call_transport(
                plan_v, spec.name, _payload_nbytes(pack)
            )
        self.transport = resolve_transport(comm, tvalue)
        # Codec resolution (DESIGN.md §10): per-call compression(...)
        # param (None value = explicit disable) > communicator default >
        # uncompressed.  Only compressible (reduction) rows accept the
        # parameter; error-feedback state rides on the param and the new
        # residual is packed into the result as `compression_state`.
        cparam = pack.get(K.COMPRESSION)
        if cparam is not None:
            self.codec = resolve_codec(comm, cparam.value)
            self._codec_state = getattr(cparam, "state", None)
            # Precomputed quantization scale (planner's hoisted scale
            # exchange, DESIGN.md §13): rides the compression(...) param.
            self._codec_scale = getattr(cparam, "scale", None)
        else:
            self.codec = resolve_codec(comm)
            self._codec_state = None
            self._codec_scale = None
        # Explicit per-call codec on an integer payload is a loud
        # trace-time error; a communicator *default* codec silently
        # skips integer payloads (they reduce exactly already).
        self._codec_explicit = cparam is not None and cparam.value is not None
        self._codec_has_state = (
            cparam is not None and getattr(cparam, "state", None) is not None
        )
        self._codec_new_state = None
        # Deterministic-schedule resolution (DESIGN.md §12): per-call
        # deterministic(...) param (None value = explicit disable) >
        # communicator default (Communicator(axis, deterministic=...)) >
        # off.  The static leaf count rides on the parameter.
        dparam = pack.get(K.DETERMINISTIC)
        if dparam is not None:
            self.deterministic = dparam.value
            self.det_leaves = getattr(dparam, "leaves", None)
        else:
            self.deterministic = getattr(comm, "deterministic_name", None)
            self.det_leaves = None
        # Op-level routing override (grid 2-hop): wins over the transport.
        self._routing = (
            getattr(comm, spec.transport_attr)
            if spec.transport_attr is not None
            else None
        )
        # Group scope (DESIGN.md §9): the communicator's group structure,
        # exposed to (plugin) lowerings that need the raw partition.
        # None = flat.  Built-in lowerings need no group-specific code:
        # `p`/`rank()` are group-relative, and the collective helpers
        # below are group-scoped via the communicator/transport.
        self.groups = getattr(comm, "groups", None)
        self._emitted: Dict[str, Any] = {}
        self._overrides: Dict[Any, Any] = {}

    # -- topology ----------------------------------------------------------
    @property
    def p(self) -> int:
        """Communicator size — the *group* size on a split communicator,
        so every count/capacity/bucket rule is group-scoped for free."""
        return self.comm.size()

    def rank(self):
        """Communicator-relative rank (group-relative when split)."""
        return self.comm.rank()

    @property
    def axis(self):
        return self.comm.axis

    # -- parameter access --------------------------------------------------
    def has(self, kind) -> bool:
        return kind in self.pack

    def value(self, kind, default=None):
        if kind in self._overrides:
            return self._overrides[kind]
        p = self.pack.get(kind)
        return p.value if p is not None else default

    def override(self, kind, value):
        """Replace a parameter's value for the rest of this lowering
        (used by the engine's capacity-policy resize)."""
        self._overrides[kind] = value

    def requested(self, kind) -> bool:
        p = self.pack.get(kind)
        return p is not None and p.is_out

    # -- transport-aware collective helpers --------------------------------
    def alltoall(self, x):
        """The op's dense personalized exchange.  A spec-level routing
        override (grid 2-hop) wins; otherwise the resolved transport
        backend moves the buckets."""
        if self._routing is not None:
            return self._routing(x)
        return self.transport.all_to_all(self.comm, x)

    def all_gather(self, x, tiled=True):
        return self.transport.all_gather(self.comm, x, tiled=tiled)

    def _active_codec(self, x):
        """The codec applying to this payload, or None.  A communicator
        default skips integer payloads; an explicit compression(...)
        parameter reaches the codec, whose payload check raises."""
        if self.codec is None:
            return None
        if not self._codec_explicit and not jnp.issubdtype(
            jnp.asarray(x).dtype, jnp.floating
        ):
            return None
        return self.codec

    def reduce(self, x, op_param):
        """Functor-mapped reduction over the resolved transport; a
        resolved codec (DESIGN.md §10) compresses sum reductions, and a
        resolved deterministic(...) schedule (DESIGN.md §12) evaluates
        the canonical tree instead of the transport's reduction."""
        codec = self._active_codec(x)
        if codec is not None:
            out, self._codec_new_state = self.comm._reduce_impl(
                x, op_param, transport=self.transport,
                codec=codec, codec_state=self._codec_state,
                codec_explicit=self._codec_explicit,
                deterministic=self.deterministic,
                det_leaves=self.det_leaves,
                codec_scale=self._codec_scale,
            )
            return out
        return self.comm._reduce_impl(
            x, op_param, transport=self.transport,
            deterministic=self.deterministic, det_leaves=self.det_leaves,
        )

    def reduce_scatter_sum(self, x):
        codec = self._active_codec(x)
        if self.deterministic is not None:
            # Deterministic reduce-scatter: the (p, chunk, ...) send
            # layout already fixes one contribution per rank, so the
            # schedule is the cross-rank tree over the full payload
            # followed by slot extraction (the per-slot additions are the
            # same canonical grouping).  A separate leaf stack has no
            # defined slot mapping here — reject it loudly.
            if self.det_leaves is not None:
                raise KampingError(
                    f"kamping.{self.spec.name}: deterministic('tree', "
                    "leaves=...) is not defined for reduce_scatter — the "
                    "(p, chunk, ...) send layout already fixes one leaf "
                    "per rank; drop leaves= (or use allreduce for leaf-"
                    "stacked payloads)"
                )
            from .reproducible import deterministic_reduce

            if codec is not None:
                full, self._codec_new_state = (
                    codec.deterministic_allreduce_sum(
                        self.comm, x, self._codec_state, leaves=None,
                        scale=self._codec_scale,
                    )
                )
            else:
                full = deterministic_reduce(self.comm, x, jnp.add)
            return lax.dynamic_index_in_dim(
                full, self.comm.rank(), 0, keepdims=False
            )
        if codec is not None:
            out, self._codec_new_state = codec.reduce_scatter_sum(
                self.comm, self.transport, x, self._codec_state,
                scale=self._codec_scale,
            )
            return out
        return self.transport.reduce_scatter_sum(self.comm, x)

    def ppermute(self, x, perm):
        """Communicator-relative ``ppermute`` — group-relative pairs map
        to one static global permutation on a split communicator."""
        return self.comm._ppermute(x, perm)

    def counts_transpose(self, sc):
        """recv_counts[j] = send_counts of rank j towards me (staged with
        the op's own transport so grid counts ride the 2-hop route)."""
        sc = jnp.asarray(sc, jnp.int32).reshape(self.p, 1)
        return self.alltoall(sc).reshape(self.p)

    # -- out-field machinery ------------------------------------------------
    def emit(self, field: str, thunk: Callable[[], Any]):
        """Offer an out-field; ``thunk`` is evaluated only if requested —
        this is how the static path stays zero-overhead."""
        self._emitted[field] = thunk

    def resolve(self, field: str):
        thunk = self._emitted.get(field)
        if thunk is None:
            if field in ("recv_counts", "recv_count"):
                raise KampingError(
                    f"kamping.{self.spec.name}: {field}_out() requires "
                    f"send_counts(...) to infer from"
                )
            raise KampingError(
                f"kamping.{self.spec.name}: {field}_out() is not inferable "
                f"for this operation; pass {field}(...) as an input instead"
            )
        return thunk()


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------
def execute(comm, spec: OpSpec, args, kw=None):
    """Collect the pack, lower the op, pack the result — for every op."""
    if kw:
        unknown = set(kw) - set(spec.kw_accepted)
        if unknown:
            raise TypeError(
                f"kamping.{spec.name}: unexpected keyword argument(s) "
                f"{sorted(unknown)}; collective arguments are the named "
                f"parameter objects (send_buf(...), send_counts(...), ...)"
                + (
                    f" — accepted keywords: {sorted(spec.kw_accepted)}"
                    if spec.kw_accepted
                    else ""
                )
            )
    pack = collect_params(
        spec.name,
        args,
        required=spec.required,
        # transport(...) is an engine-level parameter: every table row
        # accepts it (it selects how the engine moves bytes, not what the
        # op means), as is plan(...) (cost-model transport planning,
        # DESIGN.md §13).  Permute-only lowerings are transport-invariant.
        # compression(...) is engine-level too, but only the reduction
        # rows accept it (a codec encodes a sum payload; DESIGN.md §10),
        # and the same rows accept deterministic(...) (the p-invariant
        # canonical-tree schedule; DESIGN.md §12).
        accepted=tuple(spec.accepted)
        + (K.TRANSPORT, K.PLAN)
        + ((K.COMPRESSION,) if spec.compressible else ())
        + ((K.DETERMINISTIC,) if spec.deterministic else ()),
        in_place_ignored=spec.in_place_ignored,
    )
    low = Lowering(comm, spec, pack, kw or {})

    if spec.bucketed:
        _validate_and_resize_buckets(low)

    # Every instruction the op stages (its out-fields and count check
    # too) carries ``kamping.<op>`` in its op_name, so a profile of any
    # program groups device time by collective.
    with jax.named_scope(f"kamping.{spec.name}"):
        buf = spec.lower(low)

        out_fields = [("recv_buf", buf)]
        for param in pack.values():  # request order == result unpack order
            field = _OUT_FIELDS.get(param.kind)
            if field is not None and param.is_out:
                out_fields.append((field, low.resolve(field)))
        if low._codec_has_state:
            # Error-feedback round-trip (DESIGN.md §10): state went in on
            # the compression(...) parameter, the new residual comes back
            # on the result.  A None codec (explicit disable) echoes the
            # state.
            out_fields.append((
                "compression_state",
                low._codec_new_state if low._codec_new_state is not None
                else low._codec_state,
            ))

        if (
            spec.heavy_count_check
            and check_enabled(AssertionLevel.HEAVY)
            and low.has(K.SEND_COUNTS)
        ):
            buf = _stage_global_count_check(low, buf)
            out_fields[0] = ("recv_buf", buf)

    rec = ir.active()
    if rec is not None:
        # Trace-time IR capture (DESIGN.md §13): every collective issued
        # through the engine lands in the active recorder as one op with
        # its payload shape/dtype, resolved param bindings, and dep
        # edges inferred from buffer identity.  Zero overhead when no
        # recorder is active (one None check).
        ir.record_table_op(rec, comm, spec, low, pack, out_fields)

    return make_result(out_fields)


def _validate_and_resize_buckets(low: Lowering):
    """Shared bucketed-layout validation + capacity-policy application."""
    spec, p = low.spec, low.p
    x = low.value(K.SEND_BUF)
    if x is None:
        return  # in-place variant; lowering handles layout itself
    if x.ndim < 2 or x.shape[0] != p:
        hint = f" {low.spec.bucket_hint}" if spec.bucket_hint else ""
        raise KampingError(
            f"kamping.{spec.name}: send_buf must be bucketed (p, cap, ...) "
            f"with p={p}; got shape {x.shape}.{hint}"
        )
    rb = low.pack.get(K.RECV_BUF)
    policy = rb.policy if rb is not None else kp.resize_to_fit
    if isinstance(policy, kp.grow_only):
        cap, cap_r = x.shape[1], policy.capacity
        sc = low.value(K.SEND_COUNTS)
        if cap_r > cap:
            pad = [(0, 0)] * x.ndim
            pad[1] = (0, cap_r - cap)
            x = jnp.pad(x, pad)
        elif cap_r < cap:
            if check_enabled(AssertionLevel.NORMAL) and sc is not None:
                x = _check_counts_fit(x, sc, cap_r)
            x = x[:, :cap_r]
        low.override(K.SEND_BUF, x)
    # resize_to_fit / no_resize: symmetric capacity (= send capacity).


def _stage_global_count_check(low: Lowering, buf):
    """Communication-level assertion (paper §III-G): total elements sent
    == total elements received, verified globally over the communicator
    (group-scoped on a split communicator)."""
    sc = jnp.asarray(low.value(K.SEND_COUNTS))
    total_sent = low.comm._psum(jnp.sum(sc))
    total_recv = low.comm._psum(jnp.sum(low.counts_transpose(sc)))
    return _stage_equal_check(buf, total_sent, total_recv)


# --------------------------------------------------------------------------
# staged runtime checks (NORMAL / HEAVY tiers)
# --------------------------------------------------------------------------
def _check_counts_fit(x, counts, cap):
    """NORMAL-level staged assertion: counts <= capacity (overflow check).

    Poisons the buffer with NaN/sentinel on failure so the error is
    observable without host callbacks (which don't exist on TPU fast
    paths). Debug builds can use jax.debug.check instead.
    """
    ok = jnp.all(jnp.asarray(counts) <= cap)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.where(ok, x, jnp.nan)
    return jnp.where(ok, x, jnp.iinfo(x.dtype).max)


def _stage_equal_check(buf, a, b):
    ok = a == b
    if jnp.issubdtype(buf.dtype, jnp.floating):
        return jnp.where(ok, buf, jnp.nan)
    return jnp.where(ok, buf, jnp.iinfo(buf.dtype).max)


# --------------------------------------------------------------------------
# Method generation (the "composable surface is generated from the core")
# --------------------------------------------------------------------------
def _make_op_method(spec: OpSpec):
    def method(self, *args, **kw):
        return execute(self, spec, args, kw)

    method.__name__ = method.__qualname__ = spec.name
    method.__doc__ = spec.doc
    return method


def _make_nb_method(spec: OpSpec):
    def method(self, *args, **kw):
        moved = [a for a in args if isinstance(a, kp.Param) and a.moved]
        value = execute(self, spec, args, kw)
        return NonBlockingResult(value, moved_params=moved, op_name=spec.name)

    method.__name__ = method.__qualname__ = "i" + spec.name
    method.__doc__ = (
        f"Non-blocking {spec.name} (auto-generated from the op-spec "
        f"table; paper §III-E). Returns a NonBlockingResult."
    )
    return method


def attach_ops(cls, specs):
    """Register ``specs`` in OP_TABLE and attach the generated blocking
    method + non-blocking ``i*`` variant to ``cls``."""
    for spec in specs:
        existing = OP_TABLE.get(spec.name)
        if existing is not None and existing is not spec:
            raise KampingError(f"collective '{spec.name}' already registered")
        OP_TABLE[spec.name] = spec
        OP_OWNERS[spec.name] = cls.__name__
        setattr(cls, spec.name, _make_op_method(spec))
        if spec.nonblocking:
            setattr(cls, "i" + spec.name, _make_nb_method(spec))
    return cls
