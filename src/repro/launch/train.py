"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Builds the host mesh, the sharding profile from the arch's config, a
deterministic data pipeline, and runs either a plain training loop
(with optional async-checkpoint resume) or — under ``--elastic`` — the
ULFM fault-tolerant runner (DESIGN.md §15): :class:`WorldComm` +
:class:`FaultTolerantRunner`, async per-host sharded checkpointing, and
CLI failure injection for smoke-testing the shrink/restore path::

    # survive a device killed mid-collective at step 6, shrinking 2->1
    python -m repro.launch.train --arch smollm-360m --smoke --steps 12 \
        --elastic --checkpoint-dir /tmp/ck --checkpoint-every 4 \
        --inject-fail-at 6 --inject-fail-point collective
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-reduce", default="auto",
                    choices=["auto", "allreduce", "overlap", "compressed",
                             "reproducible"])
    ap.add_argument("--grad-compress", default=None,
                    choices=["int8-ef", "fp8-e4m3", "topk"])
    ap.add_argument("--transport", default=None,
                    choices=["xla", "pallas", "hier"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--save-sync", action="store_true",
                    help="block each save until durable (default: async "
                         "writer thread, the non-stall path)")
    ap.add_argument("--shards", type=int, default=1,
                    help="per-host shard files per leaf (DESIGN.md §15)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest valid snapshot from "
                         "--checkpoint-dir before training")
    ap.add_argument("--elastic", action="store_true",
                    help="run through the ULFM FaultTolerantRunner "
                         "(requires --checkpoint-dir)")
    ap.add_argument("--inject-fail-at", type=int, default=None,
                    help="inject a device failure at this step "
                         "(elastic smoke; requires --elastic)")
    ap.add_argument("--inject-fail-point", default="collective",
                    choices=["step", "collective", "checkpoint"])
    ap.add_argument("--inject-fail-count", type=int, default=1,
                    help="how many trailing devices the injection kills")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "bytes"])
    ap.add_argument("--num-layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    args = ap.parse_args(argv)
    if args.elastic and not args.checkpoint_dir:
        ap.error("--elastic requires --checkpoint-dir (recovery restores "
                 "the latest durable snapshot)")

    import jax
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.core.ulfm import WorldComm
    from repro.data import ByteCorpus, PackedLM, SyntheticLM
    from repro.launch.cache import enable_compilation_cache
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ShardingProfile
    from repro.train import (AdamWConfig, FaultTolerantRunner, TrainConfig,
                             Trainer)

    enable_compilation_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    over = {}
    if args.num_layers:
        over["num_layers"] = args.num_layers
    if args.d_model:
        over["d_model"] = args.d_model
    if over:
        cfg = dataclasses.replace(cfg, **over)

    fsdp_ok = args.grad_reduce == "auto"
    profile = ShardingProfile(
        dp_axes=("data",), tp_axis="model",
        fsdp_axes=("data",) if fsdp_ok else None,
        moe_mode=cfg.moe_mode if cfg.family == "moe" else "ep_alltoall",
    )
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps),
        grad_reduce=args.grad_reduce,
        grad_compress=args.grad_compress,
        transport=args.transport,
        microbatches=args.microbatches,
    )

    def make_pipeline():
        if args.data == "bytes":
            return PackedLM(ByteCorpus(seed=0), args.seq_len,
                            args.batch_size)
        return SyntheticLM(
            vocab_size=cfg.vocab_size, seq_len=args.seq_len,
            batch_size=args.batch_size, seed=0,
            frontend=cfg.frontend, d_model=cfg.d_model,
            num_patches=cfg.num_patches,
            encoder_seq_len=cfg.encoder_seq_len,
        )

    ckpt = (
        CheckpointManager(args.checkpoint_dir, keep=3, shards=args.shards)
        if args.checkpoint_dir else None
    )
    save_async = not args.save_sync

    # -- elastic path: ULFM runner (DESIGN.md §15) --------------------------
    if args.elastic:
        world = WorldComm(
            mesh_factory=lambda devs: make_host_mesh(devices=devs)
        )

        def make_trainer(world, restore_step):
            trainer = Trainer(cfg, world.mesh(), profile, tcfg)
            if restore_step is None:
                state = trainer.init_state(jax.random.PRNGKey(0))
            else:
                state = trainer.restore_state(ckpt, restore_step)
            return trainer, state

        def make_data(start_step, world):
            it = iter(make_pipeline())
            for _ in range(start_step):  # rewind: deterministic pipeline
                next(it)
            return it

        runner = FaultTolerantRunner(
            world, ckpt, make_trainer,
            checkpoint_every=args.checkpoint_every, save_async=save_async,
        )
        if args.inject_fail_at is not None:
            ids = [d.id for d in world.devices[-args.inject_fail_count:]]
            world.inject_failure(ids, at=args.inject_fail_point,
                                 after_step=args.inject_fail_at)
            print(f"[ft] will kill devices {ids} at "
                  f"{args.inject_fail_point!r} of step "
                  f">= {args.inject_fail_at}")
        state, losses = runner.run(make_data, args.steps)
        for e in runner.events:
            print(f"[ft] step {e.step:5d} {e.kind}: {e.detail}")
        print(f"elastic run done: world={runner.world.size()} "
              f"generation={runner.world.generation} "
              f"steps={len(losses)} last-loss={losses[-1]:.4f}")
        return 0

    # -- plain path (optional resume from the async-sharded manager) -------
    mesh = make_host_mesh()
    trainer = Trainer(cfg, mesh, profile, tcfg)
    start = 0
    if ckpt is not None and args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        state = trainer.restore_state(ckpt, start)
        print(f"resumed from step {start}")
    else:
        state = trainer.init_state(jax.random.PRNGKey(0))

    data = iter(make_pipeline())
    for _ in range(start):
        next(data)
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(state[0])
    )
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={len(jax.devices())} mesh={dict(mesh.shape)} "
          f"grad_reduce={args.grad_reduce} "
          f"grad_compress={args.grad_compress}")

    params, opt_state, extra = state
    step_fn = trainer.step_fn()
    for i in range(start, args.steps):
        batch = trainer.place_batch(next(data))
        t0 = time.perf_counter()
        params, opt_state, extra, loss, metrics = jax.block_until_ready(
            step_fn(params, opt_state, extra, batch)
        )
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            tok_s = args.batch_size * args.seq_len / dt
            print(f"step {i:5d} loss {float(loss):.4f} "
                  f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                  f"{dt*1e3:7.1f} ms/step {tok_s:9.0f} tok/s")
        if ckpt and (i + 1) % args.checkpoint_every == 0:
            trainer.save_state(ckpt, i + 1, (params, opt_state, extra),
                               async_=save_async)
    if ckpt:
        trainer.save_state(ckpt, args.steps, (params, opt_state, extra),
                           async_=save_async)
        ckpt.wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
