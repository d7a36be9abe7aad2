"""Trainer: jitted sharded train step with selectable gradient-reduction
modes, gradient accumulation, mixed precision, and fault-tolerance hooks.

Gradient-reduction modes (the paper's plugin collectives as first-class
training options):

* ``auto``          — GSPMD inserts the DP all-reduce (supports full
                      FSDP/TP/EP; the production default).
* ``allreduce``     — manual-DP shard_map island; the table-generated
                      ``Communicator.allreduce`` over a selectable
                      transport (``TrainConfig.transport``: "xla" HLOs or
                      "pallas" ring kernels — DESIGN.md §7), making the
                      kernel-level fast path selectable end-to-end.
* ``overlap``       — manual-DP shard_map island; the bucketed
                      communication–computation overlap engine
                      (``core/overlap.py``, DESIGN.md §8): gradients are
                      packed into ``bucket_bytes``-target buckets, each
                      bucket reduced with a non-blocking collective
                      tracked in a fixed-slot RequestPool
                      (``max_inflight``), so later buckets' communication
                      overlaps earlier buckets' completion work.  Rides
                      the same selectable transport as ``allreduce``.
* ``compressed``    — back-compat alias for ``allreduce`` +
                      ``grad_compress="int8-ef"`` (below).
* ``reproducible``  — alias for ``allreduce`` + the engine-level
                      ``deterministic("tree", leaves=microbatches)``
                      parameter (DESIGN.md §12): per-microbatch leaf
                      gradients reduced with the p-invariant canonical
                      tree — bitwise-identical training runs for any
                      power-of-two DP size, any transport (the tree is
                      pure ppermute), for a fixed global leaf count
                      ``M = dp_size * microbatches``.

Orthogonally, ``grad_compress`` selects a payload codec from the engine
registry (``repro.core.compression``, DESIGN.md §10) for the manual
``allreduce``/``overlap``/``reproducible`` modes: every floating-point
gradient reduction carries ``compression(codec, state=err)`` (error
feedback threaded through the op's result / the overlap engine's
RequestPool plan), and the codec composes with whatever transport moves
the bytes — ``xla``, ``pallas`` rings, or the two-level ``hier``
schedule.  Under ``reproducible`` only deterministic-capable codecs are
accepted (quantized-leaf semantics: exact tree accumulation of the
quantized partials — int8-ef / fp8-e4m3; topk's rank-dependent
scatter-add is rejected at construction time).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import operator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import (
    Communicator,
    compression,
    deterministic,
    get_codec,
    op,
    overlap_reduce_tree,
    send_buf,
)
from repro.models import Runtime, loss_and_metrics
from repro.sharding.rules import (
    ShardingProfile,
    batch_specs,
    named_shardings,
    param_specs,
)
from .optimizer import AdamWConfig, adamw_init, adamw_update

__all__ = ["TrainConfig", "Trainer", "make_train_step"]


@dataclasses.dataclass
class TrainConfig:
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    # auto | allreduce | overlap | compressed | reproducible
    grad_reduce: str = "auto"
    microbatches: int = 1  # grad accumulation steps (per device for manual)
    aux_weight: float = 0.01
    # Collective backend for the manual-DP modes' communicator
    # (None -> "xla"; "pallas" -> ring kernels; "hier" -> the two-level
    # hierarchical transport, DESIGN.md §7/§9).
    transport: Optional[str] = None
    # transport="hier" knobs (core/hier.py): ranks per intra group
    # (None -> the balanced sqrt-ish default divisor of the dp size) and
    # the per-level base backends (intra-group / cross-group).
    group_size: Optional[int] = None
    hier_intra: str = "xla"
    hier_inter: str = "xla"
    # grad_reduce="overlap" knobs (core/overlap.py, DESIGN.md §8):
    # target bytes per gradient bucket, fixed-slot in-flight bound, and
    # the per-bucket collective ("allreduce" | "reduce_scatter" — the
    # latter is the bandwidth-optimal RS+AG decomposition).
    bucket_bytes: int = 4 << 20
    max_inflight: int = 2
    overlap_mode: str = "allreduce"
    # Payload codec for the manual allreduce/overlap/reproducible
    # gradient reduction (None = uncompressed; "int8-ef" | "fp8-e4m3" |
    # "topk" | any registered codec name or Codec instance —
    # repro.core.compression, DESIGN.md §10).  Error-feedback state lives
    # in the trainer's `extra` state and is threaded through the engine
    # automatically.  Under grad_reduce="reproducible" only
    # deterministic-capable codecs compose (quantized-leaf semantics,
    # DESIGN.md §12); "topk" raises at construction.
    grad_compress: Optional[str] = None
    # grad_reduce="overlap" only: hand the bucketed reduction to the
    # trace-time planner (core/planner.py, DESIGN.md §13).  "auto" fits
    # the cost model from benchmarks/artifacts/*.json and autotunes
    # transport / bucket_bytes / mode / max_inflight; a Plan instance
    # pins the choices (its knobs override the fields above).  Every
    # planner rewrite is bitwise-neutral — planned and unplanned steps
    # produce identical parameters (tests/test_planner_equivalence.py).
    plan: Any = None
    # grad_reduce="overlap" only: reduction-order determinism mode for
    # the bucketed reduction ("tree" = the p-invariant canonical tree,
    # DESIGN.md §12).  grad_reduce="reproducible" remains the
    # whole-trainer alias; this knob composes determinism with the
    # overlap scheduler (and with the planner — plans never perturb a
    # deterministic reduction's order).
    deterministic: Optional[str] = None

    def __post_init__(self):
        # Back-compat: the pre-codec-registry mode string maps onto the
        # engine path (bitwise-identical math — tests/test_compression.py
        # pins the equivalence against the original helper).
        if self.grad_reduce == "compressed":
            self.grad_reduce = "allreduce"
            if self.grad_compress is None:
                self.grad_compress = "int8-ef"
        # reproducible + codec: only deterministic-capable codecs have
        # defined quantized-leaf semantics under the canonical tree
        # (DESIGN.md §12); topk's scatter-add order is rank-dependent, so
        # the combination is rejected here, at construction time.
        if self.grad_reduce == "reproducible" and self.grad_compress is not None:
            codec = get_codec(self.grad_compress)
            if not codec.supports_deterministic:
                raise ValueError(
                    f"TrainConfig: grad_compress={self.grad_compress!r} does "
                    "not compose with grad_reduce='reproducible': the "
                    "codec's reduction order is not p-invariant (topk's "
                    "scatter-add depends on which rank shipped each "
                    "coordinate).  Use a deterministic-capable codec "
                    "('int8-ef', 'fp8-e4m3') or drop grad_compress."
                )


def _split_microbatches(batch, m):
    return jax.tree.map(
        lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch
    )


def make_train_step(cfg, tcfg: TrainConfig, runtime: Runtime,
                    profile: ShardingProfile, mesh):
    """Returns train_step(params, opt_state, extra_state, batch)."""

    # Named scopes put each phase into its instructions' op_name, which a
    # profile groups by: ``train.forward``, its backward as
    # ``transpose(jvp(train.forward))``, ``train.reduce`` and
    # ``train.optimizer``.
    def loss_fn(params, batch):
        with jax.named_scope("train.forward"):
            return loss_and_metrics(
                params, batch, cfg, runtime, aux_weight=tcfg.aux_weight
            )

    if tcfg.grad_reduce not in ("auto", "allreduce", "overlap",
                                "reproducible"):
        raise ValueError(
            f"TrainConfig.grad_reduce={tcfg.grad_reduce!r}: expected one of "
            "'auto', 'allreduce', 'overlap', 'reproducible' (or the "
            "back-compat alias 'compressed' = allreduce + "
            "grad_compress='int8-ef')"
        )
    # Codec resolution (DESIGN.md §10): eager, so a typo is a
    # construction-time error; only the manual engine modes reduce
    # through the op-spec table where codecs live.
    grad_codec = (
        get_codec(tcfg.grad_compress) if tcfg.grad_compress is not None
        else None
    )
    if grad_codec is not None and tcfg.grad_reduce not in (
        "allreduce", "overlap", "reproducible"
    ):
        raise ValueError(
            f"TrainConfig.grad_compress={tcfg.grad_compress!r} requires "
            f"grad_reduce='allreduce', 'overlap', or 'reproducible' (got "
            f"{tcfg.grad_reduce!r}): compression is an engine-level "
            "parameter of the table-generated reductions"
        )
    if (
        grad_codec is not None
        and tcfg.grad_reduce == "reproducible"
        and not grad_codec.supports_deterministic
    ):
        # Normally caught in TrainConfig.__post_init__; re-checked here
        # for configs mutated after construction.
        raise ValueError(
            f"TrainConfig.grad_compress={tcfg.grad_compress!r} does not "
            "compose with grad_reduce='reproducible' (codec reduction "
            "order is not p-invariant); use 'int8-ef' or 'fp8-e4m3'"
        )
    # Planner / determinism knobs live in the overlap scheduler
    # (DESIGN.md §8/§13): validated eagerly so a misplaced config is a
    # construction-time error rather than a silently-ignored field.
    if tcfg.plan is not None and tcfg.grad_reduce != "overlap":
        raise ValueError(
            f"TrainConfig.plan={tcfg.plan!r} requires "
            f"grad_reduce='overlap' (got {tcfg.grad_reduce!r}): the "
            "planner schedules the bucketed reduction program"
        )
    if tcfg.deterministic is not None and tcfg.grad_reduce != "overlap":
        raise ValueError(
            f"TrainConfig.deterministic={tcfg.deterministic!r} requires "
            f"grad_reduce='overlap' (got {tcfg.grad_reduce!r}); for the "
            "whole-trainer deterministic alias use "
            "grad_reduce='reproducible'"
        )
    if (
        tcfg.deterministic is not None
        and grad_codec is not None
        and not grad_codec.supports_deterministic
    ):
        raise ValueError(
            f"TrainConfig.grad_compress={tcfg.grad_compress!r} does not "
            "compose with deterministic gradient reduction (codec "
            "reduction order is not p-invariant); use 'int8-ef' or "
            "'fp8-e4m3'"
        )

    if tcfg.grad_reduce == "auto":

        def train_step(params, opt_state, extra, batch):
            if tcfg.microbatches > 1:
                mb = _split_microbatches(batch, tcfg.microbatches)

                def acc_fn(carry, b):
                    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, b
                    )
                    gsum, lsum = carry
                    return (
                        jax.tree.map(jnp.add, gsum, jax.tree.map(
                            lambda x: x.astype(jnp.float32), g)),
                        lsum + l,
                    ), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (gsum, lsum), _ = jax.lax.scan(acc_fn, (zeros, 0.0), mb)
                grads = jax.tree.map(lambda g: g / tcfg.microbatches, gsum)
                loss = lsum / tcfg.microbatches
                metrics = {}
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, batch)
            with jax.named_scope("train.optimizer"):
                new_params, new_opt, opt_metrics = adamw_update(
                    tcfg.opt, grads, opt_state, cfg.param_dtype
                )
            return new_params, new_opt, extra, loss, {**(metrics or {}), **opt_metrics}

        return train_step

    # ---- manual-DP modes: shard_map island over the dp axes only --------
    dp_axes = profile.dp_axes
    dp_name = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    dp_set = set(dp_axes)
    # The island is manual over the dp axes, and over every other mesh
    # axis when all of those have size 1: GSPMD cannot partition a Pallas
    # kernel, even over an axis of size 1.
    if all(mesh.shape[a] == 1 for a in mesh.axis_names if a not in dp_set):
        manual_axes = set(mesh.axis_names)
    else:
        manual_axes = dp_set

    # Transport resolution (DESIGN.md §7/§9): "hier" with explicit knobs
    # becomes a configured HierTransport instance (two-level reduction:
    # intra-group reduce-scatter -> cross-group allreduce -> intra-group
    # allgather, per-level backends); plain names pass through.
    grad_transport = tcfg.transport
    if grad_transport == "hier" and (
        tcfg.group_size is not None
        or tcfg.hier_intra != "xla"
        or tcfg.hier_inter != "xla"
    ):
        from repro.core import HierTransport

        grad_transport = HierTransport(
            group_size=tcfg.group_size,
            intra=tcfg.hier_intra,
            inter=tcfg.hier_inter,
        )
    elif (
        tcfg.group_size is not None
        or tcfg.hier_intra != "xla"
        or tcfg.hier_inter != "xla"
    ):
        raise ValueError(
            f"TrainConfig.group_size/hier_intra/hier_inter are only "
            f"meaningful with transport='hier' (got "
            f"transport={tcfg.transport!r}, group_size={tcfg.group_size}, "
            f"hier_intra={tcfg.hier_intra!r}, hier_inter={tcfg.hier_inter!r})"
        )

    def microbatch_grads(params, batch):
        """Per-microbatch fp32 leaf grads + losses (shared by the manual
        modes that honor grad accumulation)."""
        mb = _split_microbatches(batch, tcfg.microbatches)

        def one(b):
            (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
            return jax.tree.map(lambda x: x.astype(jnp.float32), g), l

        return jax.lax.map(one, mb)

    def manual_grads(params, batch, err):
        """Runs inside shard_map (manual over dp): local grads + plugin
        reduction. err=None unless a codec with error feedback is on."""
        if tcfg.grad_reduce in ("allreduce", "overlap"):
            # The table-generated allreduce over the configured transport
            # (DESIGN.md §7): the gradient fast path is a backend choice,
            # not a different training loop.  "overlap" keeps the same
            # loss/grad computation but hands the reduction to the
            # bucketing scheduler (core/overlap.py, DESIGN.md §8).  A
            # grad_compress codec rides either reduction as the engine's
            # compression(...) parameter (DESIGN.md §10).
            if tcfg.microbatches > 1:
                stacked, losses = microbatch_grads(params, batch)
                grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), stacked)
                loss = jnp.mean(losses)
            else:
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, batch)
            with jax.named_scope("train.reduce"):
                comm = Communicator(dp_name, transport=grad_transport)
                inv_p = 1.0 / comm.size()
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

                new_err = None
                if tcfg.grad_reduce == "overlap":
                    if grad_codec is not None:
                        grads, new_err = overlap_reduce_tree(
                            comm, grads,
                            bucket_bytes=tcfg.bucket_bytes,
                            max_inflight=tcfg.max_inflight,
                            mode=tcfg.overlap_mode,
                            scale=inv_p,
                            compression=grad_codec,
                            err_state=err,
                            deterministic=tcfg.deterministic,
                            plan=tcfg.plan,
                        )
                    else:
                        grads = overlap_reduce_tree(
                            comm, grads,
                            bucket_bytes=tcfg.bucket_bytes,
                            max_inflight=tcfg.max_inflight,
                            mode=tcfg.overlap_mode,
                            scale=inv_p,
                            deterministic=tcfg.deterministic,
                            plan=tcfg.plan,
                        )
                elif grad_codec is not None:
                    flat_g, gdef = jax.tree.flatten(grads)
                    flat_e = gdef.flatten_up_to(err)

                    def reduce_leaf(g, e):
                        # every leaf is float32 here (cast above), so the
                        # codec applies unconditionally
                        r = comm.allreduce(
                            send_buf(g), op(operator.add),
                            compression(grad_codec, state=e),
                        )
                        return r.recv_buf * inv_p, r.compression_state

                    out = [reduce_leaf(g, e) for g, e in zip(flat_g, flat_e)]
                    grads = jax.tree.unflatten(gdef, [o[0] for o in out])
                    new_err = jax.tree.unflatten(gdef, [o[1] for o in out])
                else:
                    grads = jax.tree.map(
                        lambda g: comm.allreduce(
                            send_buf(g), op(operator.add)
                        ) * inv_p,
                        grads,
                    )
                loss = jax.lax.pmean(loss, dp_name)
            return grads, new_err, loss
        # reproducible: alias for allreduce + the engine-level
        # deterministic("tree", leaves=microbatches) parameter
        # (DESIGN.md §12).  Each microbatch gradient is one canonical
        # leaf (global leaf index = rank*microbatches + i, the global
        # data order), so the mean is bitwise independent of the DP size
        # for a fixed global leaf count M = p*microbatches — under every
        # transport (the tree is pure ppermute) and, with a quantized
        # codec, over the quantized leaf partials (exact accumulation).
        stacked, losses = microbatch_grads(params, batch)
        with jax.named_scope("train.reduce"):
            comm = Communicator(dp_name, transport=grad_transport)
            denom = tcfg.microbatches * comm.size()
            det = deterministic("tree", leaves=tcfg.microbatches)

            new_err = None
            if grad_codec is not None:
                flat_g, gdef = jax.tree.flatten(stacked)
                flat_e = gdef.flatten_up_to(err)

                def reduce_leaf_c(g, e):
                    r = comm.allreduce(
                        send_buf(g), op(operator.add), det,
                        compression(grad_codec, state=e),
                    )
                    return r.recv_buf / denom, r.compression_state

                out = [reduce_leaf_c(g, e) for g, e in zip(flat_g, flat_e)]
                grads = jax.tree.unflatten(gdef, [o[0] for o in out])
                new_err = jax.tree.unflatten(gdef, [o[1] for o in out])
            else:
                grads = jax.tree.map(
                    lambda g: comm.allreduce(
                        send_buf(g), op(operator.add), det
                    ) / denom,
                    stacked,
                )
            loss = jax.lax.pmean(jnp.mean(losses), dp_name)
        return grads, new_err, loss

    def train_step(params, opt_state, extra, batch):
        bspec = jax.tree.map(lambda _: P(profile.dp), batch)
        pspec = jax.tree.map(lambda _: P(), params)
        if grad_codec is not None:
            espec = jax.tree.map(lambda _: P(profile.dp), extra)

            def body(p_, b_, e_):
                # strip the leading dp dim of the error state inside
                e_loc = jax.tree.map(lambda x: x[0], e_)
                g, ne, l = manual_grads(p_, b_, e_loc)
                ne = jax.tree.map(lambda x: x[None], ne)
                return g, ne, l[None]

            grads, new_extra, loss = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(pspec, bspec, espec),
                out_specs=(pspec, espec, P(profile.dp)),
                axis_names=manual_axes,
                check_vma=False,
            )(params, batch, extra)
            loss = jnp.mean(loss)
        else:
            def body(p_, b_):
                g, _, l = manual_grads(p_, b_, None)
                return g, l[None]

            grads, loss = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(pspec, bspec),
                out_specs=(pspec, P(profile.dp)),
                axis_names=manual_axes,
                check_vma=False,
            )(params, batch)
            new_extra = extra
            loss = jnp.mean(loss)
        with jax.named_scope("train.optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                tcfg.opt, grads, opt_state, cfg.param_dtype
            )
        return new_params, new_opt, new_extra, loss, opt_metrics

    return train_step


class Trainer:
    """Host-side orchestration: sharded init, jitted step, checkpoint and
    fault-tolerance integration (see train.fault_tolerance)."""

    def __init__(self, cfg, mesh, profile: ShardingProfile,
                 tcfg: Optional[TrainConfig] = None, runtime=None):
        self.cfg = cfg
        self.mesh = mesh
        self.profile = profile
        self.tcfg = tcfg or TrainConfig()
        self.runtime = runtime or Runtime(
            mesh=mesh,
            tp_axis=profile.tp_axis or "model",
            batch_spec_axes=profile.dp,
            force_moe_mode=profile.moe_mode if profile.moe_mode != "ep_alltoall" else None,
        )
        self._step_fn = None
        obs.install()  # the compile log of init_state and train_step

    # -- state ----------------------------------------------------------------
    def dp_size(self) -> int:
        """Data-parallel world size on this trainer's mesh (the leading
        dimension of the EF ``extra`` state)."""
        return int(
            np.prod([self.mesh.shape[a] for a in self.profile.dp_axes])
        )

    def _state_specs(self, key=None, ep_size: int = 1):
        """(init closure, param specs, opt specs) for this mesh — shared
        by :meth:`init_state` and :meth:`restore_state` so restore
        places leaves with exactly the shardings init would have used
        (the elastic reshard onto the current mesh)."""
        from repro.models import init_params

        def init_state():
            params = init_params(
                self.cfg, key if key is not None else jax.random.PRNGKey(0),
                ep_size,
            )
            return params, adamw_init(params)

        params_shape = jax.eval_shape(init_state)
        pspecs = param_specs(
            params_shape[0], self.cfg, self.profile, self.mesh
        )
        ospecs = {
            "step": P(),
            "master": pspecs,
            "mu": pspecs,
            "nu": pspecs,
        }
        return init_state, pspecs, ospecs

    def init_state(self, key, ep_size: int = 1):
        init, pspecs, ospecs = self._state_specs(key, ep_size)
        out_shardings = (
            named_shardings(self.mesh, pspecs),
            named_shardings(self.mesh, ospecs),
        )
        params, opt_state = jax.jit(init, out_shardings=out_shardings)()
        extra = None
        if self.tcfg.grad_compress is not None:
            # Error-feedback residual, one slot per rank — and, under
            # reproducible, per canonical leaf (the residual follows the
            # leaf partitioning, so it is p-invariant too).
            lead = (self.dp_size(),)
            if self.tcfg.grad_reduce == "reproducible":
                lead = (self.dp_size(), self.tcfg.microbatches)
            extra = jax.tree.map(
                lambda p: jnp.zeros(lead + p.shape, jnp.float32), params
            )
        self.param_specs = pspecs
        self.opt_specs = ospecs
        return params, opt_state, extra

    # -- checkpoint / elastic restore (DESIGN.md §15) --------------------------
    def save_state(self, ckpt, step: int, state, *, async_: bool = False,
                   extra_meta: Optional[Dict] = None):
        """Checkpoint ``(params, opt, extra)`` with the reshard metadata
        an elastic restore needs: the saving world's dp size and
        microbatch count (the EF state's ``(dp, mb)`` provenance) ride
        in the manifest, so :meth:`restore_state` on a different-sized
        mesh knows how to fold the residuals."""
        params, opt_state, extra = state
        tree = {"params": params, "opt": opt_state}
        if extra is not None:
            tree["extra"] = extra
        meta = {
            "dp_size": self.dp_size(),
            "microbatches": self.tcfg.microbatches,
            "grad_reduce": self.tcfg.grad_reduce,
        }
        meta.update(extra_meta or {})
        ckpt.save(step, tree, extra_meta=meta, async_=async_)

    def restore_state(self, ckpt, step: Optional[int] = None):
        """Restore a :meth:`save_state` snapshot onto *this* trainer's
        mesh (the elastic-reshard path of the ULFM recovery loop).

        Params/opt are re-placed with the current mesh's shardings;
        error-feedback ``extra`` state is resharded to this mesh's
        ``(dp, mb)`` shape via :func:`repro.core.compression
        .reshard_error_feedback` — exact leaf-order-preserving reshape
        under ``reproducible`` (so ``deterministic("tree")`` runs stay
        bitwise across the resize, which requires ``microbatches`` to be
        scaled to keep the global leaf count: see
        :func:`repro.core.reproducible.elastic_leaves`), additive
        per-rank fold otherwise.  Returns ``(params, opt, extra)``.
        """
        from repro.core.compression import reshard_error_feedback
        from repro.core.errors import KampingError

        tree, meta = ckpt.restore(step)
        _, pspecs, ospecs = self._state_specs()
        params = jax.device_put(
            tree["params"], named_shardings(self.mesh, pspecs)
        )
        opt_state = jax.device_put(
            tree["opt"], named_shardings(self.mesh, ospecs)
        )
        extra = tree.get("extra")
        if extra is not None:
            saved = meta.get("extra", {})
            old_dp = int(saved.get("dp_size") or self.dp_size())
            leaf_stacked = (
                saved.get("grad_reduce", self.tcfg.grad_reduce)
                == "reproducible"
            )
            extra = reshard_error_feedback(
                extra, old_dp, self.dp_size(), leaf_stacked=leaf_stacked
            )
            if leaf_stacked:
                mb = jax.tree.leaves(extra)[0].shape[1]
                if mb != self.tcfg.microbatches:
                    raise KampingError(
                        f"restore_state: resharded EF state carries {mb} "
                        f"leaves/rank but TrainConfig.microbatches is "
                        f"{self.tcfg.microbatches} — scale microbatches "
                        "to preserve the global leaf count "
                        "(core.reproducible.elastic_leaves)"
                    )
            extra = jax.tree.map(jnp.asarray, extra)
        if self.tcfg.grad_compress is None:
            extra = None
        self.param_specs = pspecs
        self.opt_specs = ospecs
        return params, opt_state, extra

    def abort_inflight(self) -> int:
        """ULFM drain hook (DESIGN.md §15).  The jitted step's
        RequestPools live at trace time — their buckets are values
        inside the staged program, so discarding the failed step's
        *outputs* (the runner replays from the last checkpoint) is the
        drain; there is never host-side in-flight state to cancel."""
        return 0

    # -- step -----------------------------------------------------------------
    def step_fn(self):
        if self._step_fn is None:
            fn = make_train_step(
                self.cfg, self.tcfg, self.runtime, self.profile, self.mesh
            )
            self._step_fn = jax.jit(fn, donate_argnums=(0, 1, 2))
        return self._step_fn

    def place_batch(self, batch):
        specs = batch_specs(self.profile, batch)
        return jax.device_put(
            batch, named_shardings(self.mesh, specs)
        )

    def run(self, state, data_iter, steps: int, log_every: int = 10,
            health_check: Optional[Callable] = None):
        params, opt_state, extra = state
        step = self.step_fn()
        history = []
        for i in range(steps):
            if health_check is not None:
                health_check()
            batch = self.place_batch(next(data_iter))
            t0 = time.perf_counter()
            params, opt_state, extra, loss, metrics = step(
                params, opt_state, extra, batch
            )
            if i % log_every == 0 or i == steps - 1:
                l = float(loss)
                history.append((i, l, time.perf_counter() - t0))
        return (params, opt_state, extra), history
