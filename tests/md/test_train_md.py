"""Distributed training paths: grad-reduce modes, FSDP/TP parity, elastic
fault tolerance, SP decode — on 8 virtual devices."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.ulfm import WorldComm
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import ModelConfig, Runtime
from repro.sharding import ShardingProfile, named_shardings
from repro.train import AdamWConfig, TrainConfig, Trainer
from repro.train.fault_tolerance import FaultTolerantRunner

CFG = ModelConfig(
    name="t", family="dense", num_layers=4, d_model=64, num_heads=4,
    num_kv_heads=2, d_ff=128, vocab_size=256, dtype="float32",
    param_dtype="float32",
)

def _mesh(devs=None):
    devs = devs if devs is not None else jax.devices()
    n = len(devs)
    dm = max(1, n // 4)
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(n // dm, dm), ("data", "model")
    )


def _run(mode, mb, fsdp, steps=25, grad_compress=None):
    mesh = _mesh()
    profile = ShardingProfile(
        dp_axes=("data",), tp_axis="model",
        fsdp_axes=("data",) if fsdp else None,
    )
    tr = Trainer(CFG, mesh, profile,
                 TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=5,
                                             total_steps=60),
                             grad_reduce=mode, microbatches=mb,
                             grad_compress=grad_compress))
    state = tr.init_state(jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=256, seq_len=32, batch_size=16, seed=1)
    state, hist = tr.run(state, data, steps=steps, log_every=steps - 1)
    return hist


@pytest.mark.parametrize("mode,mb,fsdp", [
    ("auto", 1, True),
    ("auto", 2, True),
    ("compressed", 1, False),
    ("reproducible", 4, False),
])
def test_training_converges(mode, mb, fsdp):
    hist = _run(mode, mb, fsdp)
    assert hist[-1][1] < hist[0][1] - 0.5, (mode, hist)


def test_training_converges_reproducible_compressed():
    """grad_reduce="reproducible" + grad_compress="int8-ef": the
    quantized-leaf deterministic path (DESIGN.md §12) still learns."""
    hist = _run("reproducible", 4, False, grad_compress="int8-ef")
    assert hist[-1][1] < hist[0][1] - 0.5, hist


@pytest.mark.parametrize("grad_compress", [None, "int8-ef"])
def test_reproducible_training_bitwise_across_p(grad_compress):
    """The ISSUE-7 acceptance gate at the real-Trainer level: a short
    run with grad_reduce="reproducible" and a fixed global leaf count
    M = dp_size * microbatches = 8 yields bitwise-identical parameters
    at every power-of-two dp size (the global batch is sharded
    contiguously, so global leaf index = rank*mb + i holds the same
    rows for every p)."""
    M = 8

    def run(p, steps=4):
        devs = jax.devices()[:p]
        mesh = jax.sharding.Mesh(
            np.asarray(devs).reshape(p, 1), ("data", "model")
        )
        profile = ShardingProfile(dp_axes=("data",), tp_axis="model")
        tr = Trainer(CFG, mesh, profile,
                     TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=5,
                                                 total_steps=60),
                                 grad_reduce="reproducible",
                                 microbatches=M // p,
                                 grad_compress=grad_compress))
        state = tr.init_state(jax.random.PRNGKey(0))
        data = SyntheticLM(vocab_size=256, seq_len=32, batch_size=16,
                           seed=1)
        (params, _, _), _ = tr.run(state, data, steps=steps,
                                   log_every=steps)
        return jax.tree.map(np.asarray, params)

    ref = run(1)
    for p in (2, 4, 8):
        got = run(p)
        assert jax.tree.structure(ref) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(a, b)


def test_grad_reduce_modes_agree():
    """auto vs reproducible must produce (near-)identical trajectories;
    compressed is within quantization tolerance."""
    la = _run("auto", 1, False, steps=12)[-1][1]
    lr = _run("reproducible", 4, False, steps=12)[-1][1]
    lc = _run("compressed", 1, False, steps=12)[-1][1]
    assert abs(la - lr) < 5e-3
    assert abs(la - lc) < 5e-2


def test_fault_tolerant_elastic_shrink():
    tmp = tempfile.mkdtemp()
    ckpt = CheckpointManager(tmp, keep=2)
    world = WorldComm(mesh_factory=lambda devs: _mesh(devs))

    def make_trainer(world, restore_step):
        mesh = world.mesh()
        profile = ShardingProfile(dp_axes=("data",), tp_axis="model",
                                  fsdp_axes=("data",))
        tr = Trainer(CFG, mesh, profile,
                     TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=5,
                                                 total_steps=60)))
        params, opt, extra = tr.init_state(jax.random.PRNGKey(0))
        if restore_step is not None:
            tree, meta = ckpt.restore(restore_step)
            params = jax.device_put(
                tree["params"], named_shardings(mesh, tr.param_specs))
            opt = jax.device_put(
                tree["opt"], named_shardings(mesh, tr.opt_specs))
        return tr, (params, opt, extra)

    runner = FaultTolerantRunner(world, ckpt, make_trainer, checkpoint_every=5)
    data = SyntheticLM(vocab_size=256, seq_len=32, batch_size=16, seed=1)

    class FailingIter:
        def __init__(self, it, at):
            self.it, self.at, self.n = it, at, 0

        def __iter__(self):
            return self

        def __next__(self):
            self.n += 1
            if self.n == self.at:
                runner.world.inject_failure([4, 5, 6, 7])
            return next(self.it)

    state, losses = runner.run(FailingIter(data, 12), total_steps=20)
    kinds = [e.kind for e in runner.events]
    assert "failure" in kinds and "shrink" in kinds and "restore" in kinds
    shrink = next(e for e in runner.events if e.kind == "shrink")
    assert "4 devices" in shrink.detail
    assert losses[-1] < losses[0]  # still learning after recovery


def test_sp_decode_matches_batch_decode():
    """Sequence-parallel (flash-decode) cache sharding must match the
    plain batch-sharded decode bitwise-ish."""
    from repro.models import decode_step, init_params, prefill

    mesh = _mesh()
    cfg = CFG
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, cfg.vocab_size, (1, 8)).astype(np.int32)

    logits_ref, caches_ref = jax.jit(
        lambda p, b: prefill(p, b, cfg, max_len=16)
    )(params, {"tokens": tokens})
    step_ref = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))

    rt_sp = Runtime(mesh=mesh, tp_axis="model", batch_spec_axes="data",
                    decode_sp=True)
    logits_sp, caches_sp = jax.jit(
        lambda p, b: prefill(p, b, cfg, rt_sp, max_len=16)
    )(params, {"tokens": tokens})
    step_sp = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, rt_sp))

    np.testing.assert_allclose(np.asarray(logits_ref, np.float32),
                               np.asarray(logits_sp, np.float32),
                               atol=1e-4, rtol=1e-4)
    tok = jnp.asarray([3], jnp.int32)
    for i in range(4):
        logits_ref, caches_ref = step_ref(params, caches_ref, tok)
        logits_sp, caches_sp = step_sp(params, caches_sp, tok)
        np.testing.assert_allclose(
            np.asarray(logits_ref, np.float32),
            np.asarray(logits_sp, np.float32), atol=1e-4, rtol=1e-4,
            err_msg=f"step {i}",
        )
        tok = jnp.argmax(logits_ref[:, 0], -1).astype(jnp.int32)


def test_seq_shard_carry_preserves_loss():
    """The Megatron-SP-lite carry constraint (§Perf D1) is layout-only:
    the loss must match the unconstrained run to float tolerance."""
    from repro.models import init_params, loss_and_metrics

    mesh = _mesh()
    import dataclasses

    cfg = dataclasses.replace(CFG, d_model=64, num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(2)
    batch = {"tokens": rng.randint(1, cfg.vocab_size, (4, 32)).astype(np.int32)}

    base = Runtime(mesh=mesh, tp_axis="model", batch_spec_axes="data")
    sp = Runtime(mesh=mesh, tp_axis="model", batch_spec_axes="data",
                 seq_shard_carry=True)
    l0, _ = jax.jit(lambda p, b: loss_and_metrics(p, b, cfg, base))(params, batch)
    l1, _ = jax.jit(lambda p, b: loss_and_metrics(p, b, cfg, sp))(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
