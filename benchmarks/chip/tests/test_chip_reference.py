"""The plain float32 reference (``reference.py``) against the system's
model at smoke size on the CPU: the same weights from the same key, and
the same loss and gradient when the system computes in float32."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

from chipbench import spec  # noqa: E402

reference = spec.load_module(spec.HERE / "reference.py")
runner = spec.load_module(spec.HERE / "runners" / "trainer.py")

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=256)


class HashableDict(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@pytest.fixture(scope="module", params=[True, False],
                ids=["tied_head", "untied_head"])
def cfg(request):
    c = spec.load_cell("smollm360m.train.2k").config
    c.update(TINY, tie_word_embeddings=request.param)
    return c


def _to_reference(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for path, x in flat:
        name = runner.LEAVES[".".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path)]
        out[name] = np.asarray(x, np.float32)
    return out


def _flat(tree):
    return {".".join(str(k.key) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_weights_are_the_systems_from_the_same_key(cfg):
    from repro.models import init_params

    key = jax.random.PRNGKey(7)
    want = _to_reference(jax.jit(init_params, static_argnums=0)(
        runner.model_config(cfg), key))
    got = _flat(jax.jit(reference.init_params, static_argnums=0)(
        HashableDict(cfg), key))
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_loss_and_gradient_match_the_system_in_float32(cfg):
    """The system in float32 with XLA attention against the reference.
    Both compute the same function in float32; they differ in the order
    of sums (online softmax over key chunks against one softmax), in the
    rotary angles (float32 products against float64) and in
    ``logsumexp``'s formulation.  Each such difference is a few float32
    ulps per operation; over 2 layers and 256 logits the loss agrees to
    1e-5 relative and every gradient leaf to 1e-4 of its own norm."""
    from repro.models import init_params, loss_and_metrics

    mcfg = dataclasses.replace(runner.model_config(cfg), dtype="float32",
                               param_dtype="float32", use_pallas=False)
    key = jax.random.PRNGKey(3)
    tokens = np.random.default_rng(0).integers(1, 255, (3, 24)).astype(
        np.int32)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(runner.model_config(cfg), key))
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_and_metrics(p, {"tokens": tokens}, mcfg),
        has_aux=True))(params)
    want_g = _to_reference(grads)

    w = reference.init_params(cfg, key)
    count = float(np.sum(tokens[:, 1:] != 0))
    with jax.default_matmul_precision("highest"):
        lsum, g = jax.jit(jax.value_and_grad(reference.loss_sum),
                          static_argnums=2)(w, tokens, HashableDict(cfg))
    got_g = _flat(jax.tree.map(lambda x: x / count, g))
    np.testing.assert_allclose(float(lsum) / count, float(loss), rtol=1e-5)
    for name, want in want_g.items():
        err = np.linalg.norm(got_g[name] - want)
        assert err <= 1e-4 * np.linalg.norm(want), (name, err)


def test_fp8_control_is_not_correct():
    """The control, the reference computed in float8 in the program's
    place, fails the training cell's check at smoke size, as it does on the
    chip at the cell's size (PERF.md)."""
    cell = spec.load_cell("smollm360m.train.2k")
    cell.config.update(TINY)
    cell.traffic.update(batch=4, seq_len=16)
    ref = runner.reference_readings(cell, 11, block_rows=4)
    ctl = runner.reference_readings(cell, 11, quant="fp8", block_rows=4)
    got = runner.readings(ctl, ref, 2)
    assert any(got[k] > cell.limits[k] for k in got), got


def test_gradient_norms_from_second_moments():
    """A leaf's gradient norm at each step, from the sums of Adam's
    second moment after each step, is the norm of that step's gradient."""
    b2 = 0.95
    rng = np.random.default_rng(0)
    g = [rng.normal(size=100) * s for s in (1.0, 0.3, 2.0)]
    nu, sums = np.zeros(100), []
    for x in g:
        nu = b2 * nu + (1 - b2) * x * x
        sums.append({"a": float(nu.sum())})
    got = [n["a"] for n in runner.grad_norms(sums, b2)]
    np.testing.assert_allclose(got, [np.linalg.norm(x) for x in g],
                               rtol=1e-9)


def test_adamw_first_step_is_lr_times_sign():
    """Adam's first bias-corrected step is ``lr * g / (|g| + eps)``:
    a weight moves by the learning rate against its gradient's sign (plus
    weight decay)."""
    opt = dict(lr=1e-2, b1=0.9, b2=0.95, eps=1e-12, weight_decay=0.0,
               clip_norm=None, warmup_steps=0, total_steps=10,
               min_lr_ratio=0.1)
    w = {"a": jnp.array([1.0, 2.0, 3.0])}
    g = {"a": jnp.array([0.5, -2.0, 1e-3])}
    z = jax.tree.map(jnp.zeros_like, w)
    lr = reference.lr_at(opt, 1)
    w1, *_ = reference._adamw(w, z, z, g, 1, lr, opt)
    np.testing.assert_allclose(np.asarray(w1["a"]),
                               [1.0 - lr, 2.0 + lr, 3.0 - lr],
                               rtol=1e-6)
