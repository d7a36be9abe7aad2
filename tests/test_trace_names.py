"""The names a profile of the training step is read by: the phase scopes
in the compiled step's ``op_name`` metadata, the engine's ``kamping.<op>``
scopes, the flash backward's scope, and the compile log
(``repro.obs``)."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs

HERE = os.path.dirname(os.path.abspath(__file__))


def step_scopes(dp, grad_reduce):
    """The scopes in the op_name metadata of a tiny Trainer's compiled
    step (compiled, not run) on a mesh of ``dp`` host devices."""
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import ModelConfig
    from repro.sharding import ShardingProfile
    from repro.train import AdamWConfig, TrainConfig, Trainer

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=128,
                      dtype="float32", param_dtype="float32")
    trainer = Trainer(
        cfg, make_host_mesh(shape=(dp, 1)),
        ShardingProfile(dp_axes=("data",), tp_axis="model", fsdp_axes=None),
        TrainConfig(opt=AdamWConfig(lr=1e-3), grad_reduce=grad_reduce))
    state = trainer.init_state(jax.random.PRNGKey(0))
    batch = trainer.place_batch(next(iter(SyntheticLM(
        vocab_size=128, seq_len=16, batch_size=4, seed=1))))
    text = trainer.step_fn().lower(*state, batch).compile().as_text()
    return _scopes(text)


def _scopes(hlo_text):
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/")}


def _step_scopes_on_host_devices(dp, grad_reduce):
    """``step_scopes`` in a child process that has ``dp`` host devices:
    the device count is fixed when JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={dp}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(HERE, "..", "src"), HERE,
                    os.environ.get("PYTHONPATH", "")]))
    code = ("import json\n"
            "from test_trace_names import step_scopes\n"
            f"print(json.dumps(sorted(step_scopes({dp}, {grad_reduce!r}))))")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return set(json.loads(r.stdout.splitlines()[-1]))


@pytest.mark.parametrize("grad_reduce", ["auto", "allreduce"])
def test_step_phases_are_named(grad_reduce):
    scopes = step_scopes(1, grad_reduce)
    assert {"jvp(train.forward)", "transpose(jvp(train.forward))",
            "train.optimizer"} <= scopes, sorted(scopes)
    if grad_reduce == "auto":
        assert "train.reduce" not in scopes


def test_engine_reduce_is_named_at_dp2():
    scopes = _step_scopes_on_host_devices(2, "allreduce")
    assert {"jvp(train.forward)", "transpose(jvp(train.forward))",
            "train.reduce", "kamping.allreduce",
            "train.optimizer"} <= scopes, sorted(scopes)


def test_flash_backward_is_named():
    from repro.models.layers import flash_attention

    q = jnp.ones((1, 16, 2, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 8).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    # The scope wraps a vjp, so its ops read
    # ``transpose(jvp(attn.flash_bwd))/...``.
    assert "transpose(jvp(attn.flash_bwd))" in _scopes(text)


def test_compile_log_counts_backend_compiles():
    obs.install()
    obs.install()  # a second call adds no second listener
    obs.reset()

    def probe_twice(x):
        return x * 2 + 1

    def probe_shapes(x):
        return x - 1

    once, shapes = jax.jit(probe_twice), jax.jit(probe_shapes)
    once(np.ones(3, np.float32)).block_until_ready()
    once(np.ones(3, np.float32)).block_until_ready()
    shapes(np.ones(3, np.float32)).block_until_ready()
    shapes(np.ones(4, np.float32)).block_until_ready()
    log = obs.compile_log()
    assert log["probe_twice"]["compiles"] == 1
    assert log["probe_twice"]["traces"] == 1
    assert log["probe_twice"]["lowerings"] == 1
    assert log["probe_shapes"]["compiles"] == 2
    for entry in (log["probe_twice"], log["probe_shapes"]):
        assert entry["compile_s"] > 0 and entry["lower_s"] > 0
    log["probe_twice"]["compiles"] = 99  # a copy: the log is unchanged
    assert obs.compile_log()["probe_twice"]["compiles"] == 1
    obs.reset()
    assert "probe_twice" not in obs.compile_log()


def test_trainer_logs_its_programs():
    obs.reset()
    step_scopes(1, "auto")
    log = obs.compile_log()
    assert log["init_state"]["compiles"] == 1
    assert log["train_step"]["compiles"] == 1
