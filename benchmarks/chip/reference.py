"""Plain float32 reference of a dense decoder-only language model: its
weights from the seed, its loss, its gradient and AdamW.

It is the yardstick of the training cells' correctness check and imports
nothing of the system under test.  The model follows the configuration
file's published keys (``hidden_size``, ``num_hidden_layers``, ...):

* token embedding (a lookup), then per layer a pre-norm residual block:
  RMSNorm, grouped-query causal self-attention with rotary position
  embedding (rotate-half form, ``rope_theta``), RMSNorm, SiLU-gated MLP;
* a final RMSNorm and the output head: the embedding's transpose where
  ``tie_word_embeddings`` is true, a weight of its own otherwise; the
  loss is the mean cross-entropy of each next token over positions whose
  target is not 0.

Each RMSNorm scales by ``1 + w`` with ``w`` starting at 0, which is the
published ``w`` starting at 1 written another way.

Weights are drawn as the system under test draws them, re-derived here
from the key: truncated normals in [-2, 2] scaled by ``1/sqrt(fan_in)``
(embeddings by 0.02), norm offsets 0.  Training keeps float32 master
weights and computes each step's loss and gradient at the master weights
rounded to the type the configuration serves them in (``program.
param_dtype``, bfloat16), as mixed-precision training does; the initial
master weights are themselves rounded so.

Everything is computed in float32 with every matrix product at
``Precision.HIGHEST``, attention materialised as a full masked softmax,
layer by layer with recomputation and over the batch in blocks of rows so
that it fits one chip.  ``quant="fp8"`` rounds both operands of every
matrix product to float8 e4m3 with a per-tensor scale, and their
gradients to e5m2: the benchmark's control, computed in the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def _served(cfg):
    """Rounds float32 weights to the type they are served in."""
    dtype = jnp.dtype(cfg["program"]["param_dtype"])
    return lambda w: w.astype(dtype).astype(jnp.float32)


def init_params(cfg, key):
    """float32 weights, representable in the served type, from ``key``."""
    L, d, H, KV, D, ff, V = _sizes(cfg)
    served = _served(cfg)

    def tn(k, shape, scale):
        w = jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
        return served(w * scale)

    def layer(k):
        ks = jax.random.split(k, 4)
        a = jax.random.split(ks[0], 4)
        m = jax.random.split(ks[1], 3)
        s_d, s_q, s_f = 1 / math.sqrt(d), 1 / math.sqrt(H * D), \
            1 / math.sqrt(ff)
        return {
            "ln1": jnp.zeros((d,), jnp.float32),
            "wq": tn(a[0], (d, H * D), s_d),
            "wk": tn(a[1], (d, KV * D), s_d),
            "wv": tn(a[2], (d, KV * D), s_d),
            "wo": tn(a[3], (H * D, d), s_q),
            "ln2": jnp.zeros((d,), jnp.float32),
            "wi": tn(m[0], (d, ff), s_d),
            "wg": tn(m[1], (d, ff), s_d),
            "w_down": tn(m[2], (ff, d), s_f),
        }

    keys = jax.random.split(key, 8)
    layer_keys = jax.random.split(jax.random.split(keys[1], 1)[0], L)
    params = {
        "embed": tn(keys[0], (V, d), 0.02),
        "layers": jax.vmap(layer)(layer_keys),
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = tn(keys[3], (d, V), 1 / math.sqrt(d))
    return params


# -- the control's rounding ---------------------------------------------------
def _round(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _einsum(spec, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# -- forward and loss -----------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rotary(S, D, theta):
    inv = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ang = np.arange(S)[:, None] * inv[None, :]  # float64
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):  # x: (b, S, heads, D)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(cfg, quant, cos, sin, x, p):
    _, d, H, KV, D, _, _ = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    b, S, _ = x.shape
    h = _rms(x, p["ln1"], eps)
    q = _einsum("bsd,de->bse", h, p["wq"], quant).reshape(b, S, KV, H // KV, D)
    k = _einsum("bsd,de->bse", h, p["wk"], quant).reshape(b, S, KV, D)
    v = _einsum("bsd,de->bse", h, p["wv"], quant).reshape(b, S, KV, D)
    q = _rope(q.reshape(b, S, H, D), cos, sin).reshape(b, S, KV, H // KV, D)
    k = _rope(k, cos, sin)
    s = _einsum("bqkgd,bskd->bkgqs", q, k, quant) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _einsum("bkgqs,bskd->bqkgd", a, v, quant).reshape(b, S, H * D)
    x = x + _einsum("bse,ed->bsd", o, p["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    g = _einsum("bsd,df->bsf", h, p["wg"], quant)
    u = _einsum("bsd,df->bsf", h, p["wi"], quant)
    return x + _einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], quant)


def loss_sum(params, tokens, cfg, quant=None):
    """Sum over the rows of ``tokens`` of the next-token cross-entropy at
    positions whose target is not 0."""
    S = tokens.shape[1]
    cos, sin = _rotary(S, cfg["head_dim"], cfg["rope_theta"])
    body = jax.checkpoint(
        lambda x, p: (_layer(cfg, quant, cos, sin, x, p), None))
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(body, x, params["layers"])
    h = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        logits = _einsum("bsd,vd->bsv", h[:, :-1], params["embed"], quant)
    else:
        logits = _einsum("bsd,dv->bsv", h[:, :-1], params["lm_head"], quant)
    targets = tokens[:, 1:]
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - gold
    return jnp.sum(jnp.where(targets != 0, nll, 0.0))


# -- AdamW --------------------------------------------------------------------
def lr_at(opt, step):
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def _adamw(w, m, v, g, step, lr, opt):
    """One AdamW step with global-norm clipping and decoupled weight decay
    on every leaf; returns (w, m, v, clipped gradient)."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    if opt["clip_norm"] is not None:
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"]
                                      / jnp.maximum(norm, 1e-9)), g)
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
                                  + opt["weight_decay"] * w), w, m, v)
    return w, m, v, g


def _names(tree):
    return [".".join(str(k.key) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(tree)]


def leaf_norms(tree):
    """{leaf name: L2 norm} with names joined by '.'."""
    return {n: float(v) for n, v in zip(_names(tree), _norms(tree))}


def train(cfg, opt, key, batches, quant=None, block_rows=1):
    """Follow ``len(batches)`` AdamW steps from the weights of ``key``.

    Returns the loss of each step, the per-leaf norms of each step's
    gradient after clipping (what the optimizer is given) and the per-leaf
    norms of the master weights' change over all the steps.  ``block_rows``
    rows of a batch go through the model at a time, so that a batch of
    long rows fits one chip."""
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(
            functools.partial(loss_sum, cfg=cfg, quant=quant)))
        acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)

        def mean_update(w, m, v, gsum, count, step, lr):
            g = jax.tree.map(lambda x: x / count, gsum)
            return _adamw(w, m, v, g, step, lr, opt)

        update = jax.jit(mean_update, donate_argnums=(0, 1, 2, 3))
        init = jax.jit(functools.partial(init_params, cfg))
        zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
        served = jax.jit(lambda w: jax.tree.map(_served(cfg), w))
        w = init(key)
        m, v = zeros(w), zeros(w)
        losses, grads = [], []
        for step, tokens in enumerate(batches, 1):
            tokens = np.asarray(tokens)
            count = float(np.sum(tokens[:, 1:] != 0))
            total, gsum, ws = 0.0, None, served(w)
            for r in range(0, tokens.shape[0], block_rows):
                l, g = grad(ws, tokens[r:r + block_rows])
                total += float(l)
                gsum = g if gsum is None else acc(gsum, g)
            del ws
            w, m, v, g = update(w, m, v, gsum, count, step,
                                lr_at(opt, step))
            grads.append(leaf_norms(g))
            del g, gsum
            losses.append(total / count)
        del m, v
        delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                        donate_argnums=0)(w, init(key))
        return {"losses": losses, "grad_norms": grads,
                "delta_norms": leaf_norms(delta)}
