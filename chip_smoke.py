"""Bring-up check on a TPU: the engine-routed trainer and server at
smollm-360m width, and the engine's collectives across chips.

    python chip_smoke.py             # one chip: kernel, train, serve
    python chip_smoke.py --chips 4   # four chips: collectives, dp=4 step

Each phase prints one JSON line with its shapes, the device's peak memory
and its seconds; the last line is ``{"ok": true, "device": {...}}``.  A
phase that fails raises and the script exits non-zero.  With no TPU it
exits non-zero before any phase: there is no CPU fallback.  Weights and
data are random, made from ``--seed``.  Times printed here label the
device they ran on and are not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import operator
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-360m"
SEQ = 2048
# Train batches tried largest first; the step runs at the first whose
# compiled program fits the chip (>= 8k tokens per step).
TRAIN_BATCHES = (10, 8, 6, 4)
TRAIN_STEPS = 6  # the first compiles; at least five more are timed

# Serving: 16 requests of mixed prompt lengths in three prefill buckets
# (64, 256 and 1024 rows with 16-row pages), so the engine compiles three
# prefill programs; prompt + budget stays within max_len.
SERVE_MAX_LEN = 2048
SERVE_SLOTS = 8
SERVE_PAGE = 16
SERVE_SPECS = [  # (prompt length, max_new_tokens)
    (40, 24), (64, 8), (200, 16), (1000, 32), (33, 48), (256, 4),
    (700, 12), (150, 40), (513, 20), (45, 1), (129, 30), (900, 64),
    (60, 10), (180, 6), (800, 28), (50, 36),
]
REPLAY = 2  # the request whose logits are checked against a forward pass

COLLECTIVE_BYTES = (4 << 10, 1 << 20, 64 << 20)
PALLAS_REPEATS = 3


def emit(rec):
    print(json.dumps(rec), flush=True)


class Phase:
    """Times a phase and records the device's peak memory after it."""

    def __init__(self, name, device):
        self.rec = {"phase": name}
        self.device = device

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self.rec

    def __exit__(self, kind, *_):
        if kind is None:
            self.rec["seconds"] = time.perf_counter() - self.t0
            stats = self.device.memory_stats() or {}
            self.rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
            emit(self.rec)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------
def kernel_phase(rec, seed, B=4):
    """The flash-attention kernel at smollm-360m widths vs chunked_attention."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.models.layers import chunked_attention

    cfg = get_config(ARCH)
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, SEQ, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, SEQ, KV, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, SEQ, KV, D), jnp.bfloat16)
    kernel = jax.jit(
        lambda q, k, v: flash_ops.flash_attention(q, k, v, causal=True)
    ).lower(q, k, v).compile()
    assert "tpu_custom_call" in kernel.as_text(), "kernel not compiled in"
    out = np.asarray(kernel(q, k, v), np.float32)
    ref = np.asarray(jax.jit(
        lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=512)
    )(q, k, v), np.float32)
    # Both compute the softmax and the weighted sum in fp32 from the same
    # bf16 inputs and round the output to bf16 once; they differ in the
    # order of the fp32 sums and in XLA's bf16 matmul passes.  bf16 keeps
    # 8 significant bits (0.4%): allow a few such steps.
    err = np.abs(out - ref)
    rec.update(shape=list(out.shape), max_abs_err=float(err.max()),
               tpu_custom_call=True)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


def _fits(compiled, device, resident):
    """Whether a compiled program fits the device, given ``resident``
    bytes of its arguments that are already allocated there."""
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    stats = device.memory_stats()
    free = stats["bytes_limit"] - stats["bytes_in_use"] + resident
    return need <= free, need


def train_phase(rec, cfg, device, seed, batches=TRAIN_BATCHES,
                steps=TRAIN_STEPS):
    """smollm-360m through Trainer, engine allreduce over the xla
    transport, on the one-chip mesh; returns the trained params."""
    import jax

    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import loss_and_metrics
    from repro.sharding import ShardingProfile
    from repro.train import AdamWConfig, TrainConfig, Trainer

    mesh = make_host_mesh()
    profile = ShardingProfile(dp_axes=("data",), tp_axis="model",
                              fsdp_axes=None)
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=3e-4, warmup_steps=2, total_steps=steps),
        grad_reduce="allreduce", transport="xla", microbatches=1,
    )
    trainer = Trainer(cfg, mesh, profile, tcfg)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    resident = sum(x.nbytes for x in jax.tree.leaves(state))
    step = trainer.step_fn()
    tried = {}
    for B in batches:
        batch = trainer.place_batch(
            next(SyntheticLM(cfg.vocab_size, SEQ, B, seed=seed))
        )
        try:
            ok, need = _fits(step.lower(*state, batch).compile(), device,
                             resident)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            ok, need = False, None
        tried[B] = need
        if ok:
            break
    else:
        raise RuntimeError(f"no train batch fits the chip: {tried}")
    rec.update(mesh=dict(mesh.shape), batch=[B, SEQ], tokens_per_step=B * SEQ,
               bytes_needed_by_batch=tried)

    # The first loss against the same step's loss with XLA attention.
    data = SyntheticLM(cfg.vocab_size, SEQ, B, seed=seed)
    first = trainer.place_batch(next(SyntheticLM(cfg.vocab_size, SEQ, B,
                                                 seed=seed)))
    xla_cfg = dataclasses.replace(cfg, use_pallas=False)
    ref_loss = float(jax.jit(
        lambda p, b: loss_and_metrics(p, b, xla_cfg, trainer.runtime)[0]
    )(state[0], first))
    del first

    state, history = trainer.run(state, iter(data), steps, log_every=1)
    losses = [h[1] for h in history]
    rec.update(losses=losses, loss_xla_attention=ref_loss,
               step_seconds=[h[2] for h in history[1:]],
               step_seconds_device=device.device_kind)
    assert len(losses) == steps and all(np.isfinite(losses)), losses
    # bf16 keeps 8 significant bits; the kernel and XLA attention round
    # differently in each of 32 layers, and the loss averages those
    # differences over every token of the batch: 0.2% of a loss near
    # ln(vocab) = 10.8 is 0.02.
    assert abs(losses[0] - ref_loss) <= 2e-2, (losses[0], ref_loss)
    return state[0]


def _engine_replay_logits(engine, req):
    """Logits of the engine's path for one finished request: its bucket-
    padded prefill, then paged decode over the tokens it generated."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step_paged, init_paged_caches, prefill

    cfg, ps, max_len = engine.cfg, engine.page_size, engine.max_len
    n = len(req.prompt)
    bucket = engine._bucket(n)
    cache_len = -(-bucket // ps) * ps
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = req.prompt
    logits, pcache = jax.jit(
        lambda p, t, tl: prefill(p, {"tokens": t}, cfg, max_len=cache_len,
                                 true_len=tl)
    )(engine.params, toks, np.asarray([n], np.int32))
    out = [np.asarray(logits[0, 0], np.float32)]

    # One slot whose logical page i is physical page i + 1.
    pages = max_len // ps
    caches = init_paged_caches(cfg, 1, pages + 1, ps, max_len)
    assert not caches["rem"], "replay covers stacked layers only"

    def fill(pool, rows):  # (units, P, ps, KV, D) <- (units, 1, len, KV, D)
        r = rows[:, 0].reshape((rows.shape[0], -1, ps) + rows.shape[3:])
        return pool.at[:, 1:1 + r.shape[1]].set(r)

    caches["units"] = [jax.tree.map(fill, c, pc)
                       for c, pc in zip(caches["units"], pcache["units"])]
    caches["pos"] = pcache["pos"]
    caches["block_tables"] = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, c, t: decode_step_paged(p, c, t, cfg))
    for t in req.generated[:-1]:
        logits, caches = step(engine.params, caches,
                              jnp.asarray([t], jnp.int32))
        out.append(np.asarray(logits[0, 0], np.float32))
    return np.stack(out)


def serve_phase(rec, cfg, params, seed):
    """The same model through ServeEngine: paged KV, 8 slots, 16 requests."""
    import jax

    from repro.models.transformer import forward_train, lm_logits
    from repro.serve import Request, ServeEngine

    rng = np.random.RandomState(seed)
    reqs = [Request(prompt=rng.randint(1, cfg.vocab_size, (n,)).astype(
        np.int32), max_new_tokens=m, rid=i)
        for i, (n, m) in enumerate(SERVE_SPECS)]
    engine = ServeEngine(cfg, params, max_len=SERVE_MAX_LEN,
                         num_slots=SERVE_SLOTS, num_replicas=1,
                         replica_shards=1, kv_layout="paged",
                         page_size=SERVE_PAGE, plan=None)
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run_to_completion(max_steps=10_000)
    rec.update(requests=len(reqs), finished=len(done),
               truncated=engine.truncated, engine_steps=engine.counters["steps"],
               decode_tokens=engine.counters["decode_tokens"],
               prefill_programs=engine.prefill_cache_size(),
               run_seconds=time.perf_counter() - t0,
               run_seconds_device=jax.devices()[0].device_kind)
    assert len(done) == len(reqs) and not engine.truncated
    for r in reqs:
        assert len(r.generated) == r.max_new_tokens, (r.rid, len(r.generated))

    # Teacher-forced logits of the same tokens (prompt + generated).
    req = reqs[REPLAY]
    got = _engine_replay_logits(engine, req)
    seq = np.concatenate([req.prompt, np.asarray(req.generated[:-1],
                                                 np.int32)])[None]
    want = jax.jit(
        lambda p, t: lm_logits(p, forward_train(p, {"tokens": t}, cfg)[0],
                               cfg)
    )(params, seq)
    n = len(req.prompt)
    want = np.asarray(want[0, n - 1:], np.float32)
    err = np.abs(got - want)
    scale = float(np.abs(want).max())
    gen = np.asarray(req.generated)
    gap = want.max(-1) - want[np.arange(len(gen)), gen]
    rec.update(replay_request=req.rid, replay_positions=int(got.shape[0]),
               logits_shape=list(got.shape), max_abs_err=float(err.max()),
               mean_abs_err=float(err.mean()), logit_scale=scale,
               max_token_gap=float(gap.max()))
    # bf16 model: the prefill, paged-decode and teacher-forced paths round
    # differently in each of 32 layers.  A wrong page, position or cache
    # row moves logits by the order of their whole scale; rounding moves
    # them by a few percent of it.  Every generated token must be the
    # reference's top token up to the same margin.
    assert err.max() <= 0.05 * scale, (err.max(), scale)
    assert err.mean() <= 0.01 * float(np.abs(want).mean()), err.mean()
    assert gap.max() <= 0.05 * scale, (gap.max(), scale)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------
def _collective_cases(p, nbytes, rng):
    """(name, fn, per-rank inputs, NumPy expected per-rank outputs)."""
    from repro.core import (Communicator, op, recv_counts_out, send_buf,
                            send_counts)

    n = nbytes // 4
    lo, hi = -(1 << 20), 1 << 20  # sums of p int32 values stay exact
    cases = []

    def allreduce(t, v):
        return Communicator("x", transport=t).allreduce(send_buf(v),
                                                        op(operator.add))

    payloads = [rng.randint(lo, hi, (p, n)).astype(np.int32)]
    # The narrow widths the ring kernels move: int8 sums wrap alike on
    # both sides; bf16 quarters in [-4, 4) sum exactly over 4 ranks.  Up
    # to 1 MiB only: at 64 MiB the relayouts around the ring kernels take
    # one to two minutes to compile for 8- and 16-bit payloads.
    if nbytes <= 1 << 20:
        import ml_dtypes

        payloads += [
            rng.randint(-128, 128, (p, nbytes)).astype(np.int8),
            (rng.randint(-16, 16, (p, nbytes // 2)) / 4).astype(
                ml_dtypes.bfloat16),
        ]
    for x in payloads:
        name = "allreduce" if x.dtype == np.int32 else \
            f"allreduce_{x.dtype.name}"
        cases.append((name, allreduce, (x,), [x.sum(0, dtype=x.dtype)] * p))

    x = rng.randint(lo, hi, (p, p, n // p)).astype(np.int32)
    cases.append((
        "reduce_scatter",
        lambda t, v: Communicator("x", transport=t).reduce_scatter(
            send_buf(v), op(operator.add)),
        (x,), [x[:, r].sum(0, dtype=np.int32) for r in range(p)],
    ))

    x = rng.randint(lo, hi, (p, n)).astype(np.int32)

    def allgatherv(t, v):
        r = Communicator("x", transport=t).allgatherv(send_buf(v),
                                                      recv_counts_out())
        return r.recv_buf, r.recv_counts

    cases.append((
        "allgatherv", allgatherv, (x,),
        [(x.reshape(-1), np.full((p,), n, np.int32))] * p,
    ))

    cap = n // p
    x = rng.randint(lo, hi, (p, p, cap)).astype(np.int32)
    sc = np.asarray([[(i + j) % 3 * cap // 2 for j in range(p)]
                     for i in range(p)], np.int32)

    def alltoallv(t, v, c):
        r = Communicator("x", transport=t).alltoallv(
            send_buf(v), send_counts(c), recv_counts_out())
        return r.recv_buf, r.recv_counts

    cases.append((
        "alltoallv", alltoallv, (x, sc),
        [(x[:, r], sc[:, r]) for r in range(p)],
    ))
    return cases


def collectives_phase(rec, devices, seed, sizes=COLLECTIVE_BYTES,
                      repeats=PALLAS_REPEATS):
    """Engine collectives on a real mesh, each transport vs NumPy."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import HierTransport

    p = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))
    transports = {"xla": "xla", "pallas": "pallas",
                  "hier": HierTransport(group_size=2)}
    rng = np.random.RandomState(seed)
    results = []
    for nbytes in sizes:
        for name, fn, inputs, want in _collective_cases(p, nbytes, rng):
            flat_in = [a.reshape((-1,) + a.shape[2:]) for a in inputs]
            for tname, t in transports.items():
                def body(*args, t=t, fn=fn):
                    out = fn(t, *args)
                    return jax.tree.map(lambda a: a[None], out)

                prog = jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=(P("x"),) * len(flat_in),
                    out_specs=P("x"), check_vma=False,
                ))
                for _ in range(repeats if tname == "pallas" else 1):
                    out = jax.tree.map(np.asarray, prog(*flat_in))
                    outs = out if isinstance(out, tuple) else (out,)
                    for r in range(p):
                        exp = want[r] if isinstance(want[r], tuple) \
                            else (want[r],)
                        for o, e in zip(outs, exp):
                            np.testing.assert_array_equal(
                                o[r], e, err_msg=f"{name} {tname} "
                                f"{nbytes} B rank {r}")
                results.append([name, tname, nbytes])
    rec.update(devices=p, checked=len(results),
               ops=sorted({r[0] for r in results}),
               transports=list(transports), sizes_bytes=list(sizes),
               pallas_repeats=repeats, bitwise_equal_numpy=True)


def _one_step(cfg, devices, dp, transport, batch_np, seed):
    """One engine-allreduce step of ``cfg`` at data parallelism ``dp``;
    returns (loss, lr, grad mean mu, master weights) on the host."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ShardingProfile
    from repro.train import AdamWConfig, TrainConfig, Trainer

    mesh = make_host_mesh(shape=(dp, 1), devices=devices[:dp])
    profile = ShardingProfile(dp_axes=("data",), tp_axis="model",
                              fsdp_axes=None)
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-4, warmup_steps=2,
                                       total_steps=10),
                       grad_reduce="allreduce", transport=transport)
    trainer = Trainer(cfg, mesh, profile, tcfg)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    batch = trainer.place_batch(batch_np)
    _, opt, _, loss, metrics = trainer.step_fn()(*state, batch)
    host = (float(loss), float(metrics["lr"]),
            [np.asarray(a) for a in jax.tree.leaves(opt["mu"])],
            [np.asarray(a) for a in jax.tree.leaves(opt["master"])])
    del state, opt, batch
    return host


def dp4_phase(rec, cfg, devices, seed, batch=4):
    """The smollm-360m engine-allreduce step at dp=4 over xla and pallas
    against dp=1 on the same global batch."""
    from repro.data import SyntheticLM

    data = next(SyntheticLM(cfg.vocab_size, SEQ, batch, seed=seed))
    ref_loss, lr, ref_mu, ref_w = _one_step(cfg, devices, 1, "xla", data,
                                            seed)
    mu_norm = np.sqrt(sum(float(np.square(m, dtype=np.float64).sum())
                          for m in ref_mu))
    rec.update(global_batch=[batch, SEQ], loss_dp1=ref_loss, lr=lr)
    for transport in ("xla", "pallas"):
        loss, _, mu, w = _one_step(cfg, devices, 4, transport, data, seed)
        dmu = np.sqrt(sum(float(np.square(a - b, dtype=np.float64).sum())
                          for a, b in zip(mu, ref_mu)))
        dw = max(float(np.abs(a - b).max()) for a, b in zip(w, ref_w))
        rec[transport] = {"loss": loss, "grad_rel_l2": dmu / mu_norm,
                          "max_weight_diff": dw}
        # Same tokens, split four ways: the bf16 forward and backward
        # round differently per shard and the mean is summed in another
        # order.  The loss agrees to bf16 precision; the averaged
        # gradient (Adam's first moment) within 2% in L2.  Adam's first
        # step moves each weight by about lr * sign(grad), so a gradient
        # that rounds to the other sign moves it by at most 2 * lr.
        assert abs(loss - ref_loss) <= 1e-2, (transport, loss, ref_loss)
        assert dmu <= 2e-2 * mu_norm, (transport, dmu, mu_norm)
        assert dw <= 2 * lr * (1 + 1e-3) + 1e-6, (transport, dw, lr)


# --------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.cache import enable_compilation_cache

    emit({"compilation_cache": enable_compilation_cache()})
    cfg = dataclasses.replace(get_config(ARCH), use_pallas=True)
    if args.chips == 4:
        with Phase("collectives", dev) as rec:
            collectives_phase(rec, devices[:4], args.seed)
        with Phase("train_dp4", dev) as rec:
            dp4_phase(rec, cfg, devices, args.seed)
        count = 4
    else:
        with Phase("kernel", dev) as rec:
            kernel_phase(rec, args.seed)
        with Phase("train", dev) as rec:
            params = train_phase(rec, cfg, dev, args.seed)
        with Phase("serve", dev) as rec:
            serve_phase(rec, cfg, params, args.seed)
        count = 1
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind, "count": count}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
