"""Shared neural building blocks (functional style, explicit param pytrees)."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm",
    "make_rotary",
    "apply_rotary",
    "init_dense",
    "dense",
    "init_mlp",
    "gated_mlp",
    "causal_conv1d",
    "chunked_attention",
    "flash_attention",
    "decode_attention",
    "init_attention",
    "attention_forward",
    "attention_decode",
]


def _dtype(name):
    return jnp.dtype(name)


def rms_norm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# -- rotary -------------------------------------------------------------------
def make_rotary(positions, head_dim, theta=10000.0):
    """positions: (...,) int -> (cos, sin) of shape (..., head_dim//2)."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D//2) or (S, D//2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(dt)


# -- dense / mlp --------------------------------------------------------------
def init_dense(key, d_in, d_out, bias=False, dtype="bfloat16", scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.truncated_normal(key, -2, 2, (d_in, d_out), jnp.float32)
    p = {"w": (w * scale).astype(_dtype(dtype))}
    if bias:
        p["b"] = jnp.zeros((d_out,), _dtype(dtype))
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_mlp(key, d_model, d_ff, dtype="bfloat16"):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": init_dense(k1, d_model, d_ff, dtype=dtype),
        "wg": init_dense(k2, d_model, d_ff, dtype=dtype),
        "wo": init_dense(k3, d_ff, d_model, dtype=dtype),
    }


def gated_mlp(p, x, act="silu"):
    a = dense(p["wi"], x)
    g = dense(p["wg"], x)
    actfn = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    return dense(p["wo"], actfn(g) * a)


# -- depthwise causal conv ----------------------------------------------------
def causal_conv1d(x, w, state=None):
    """Depthwise causal conv. x: (B, S, C); w: (K, C).

    Returns (y, new_state) where state is the trailing (K-1, ...) inputs —
    the decode carry. With ``state`` given and S==1 this is the decode step.
    """
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+K-1, C)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    new_state = xp[:, -(K - 1) :, :] if K > 1 else jnp.zeros_like(pad)
    return y.astype(x.dtype), new_state


# -- memory-efficient attention (XLA path; Pallas kernel is the TPU path) -----
def chunked_attention(
    q,
    k,
    v,
    *,
    q_offset=0,
    causal=True,
    window: Optional[int] = None,
    chunk: int = 512,
):
    """Online-softmax attention, scanning over KV chunks (flash-style in XLA).

    q: (B, Sq, H, D); k/v: (B, Skv, KV, D) with H % KV == 0 (GQA).
    ``window``: sliding-window width (None = full); causal uses absolute
    positions q_pos = q_offset + i, k_pos = j.
    Memory: O(Sq · chunk) per head instead of O(Sq · Skv).
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
    scale = 1.0 / math.sqrt(D)

    chunk = min(chunk, Skv)
    n_chunks, rem = divmod(Skv, chunk)
    if rem:  # pad KV to a multiple of chunk; padded keys are masked off
        pad = chunk - rem
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        n_chunks += 1
    kc = k.reshape(B, n_chunks, chunk, KV, D)
    vc = v.reshape(B, n_chunks, chunk, KV, D)

    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, start = inp  # (B, chunk, KV, D), (B, chunk, KV, D), ()
        s = jnp.einsum(
            "bqkgd,bckd->bqkgc", qg, kb.astype(jnp.float32)
        ) * scale  # (B,Sq,KV,G,chunk)
        k_pos = start + jnp.arange(chunk)
        mask = k_pos[None, :] <= q_pos[:, None] if causal else jnp.ones(
            (Sq, chunk), bool
        )
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        mask = mask & (k_pos[None, :] < Skv)  # padded tail
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use 0
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, D), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), starts),
    )
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal, window, chunk):
    """The Pallas flash-attention kernel, differentiable.

    The kernel has no backward pass of its own: the gradient is that of
    :func:`chunked_attention`, which computes the same function in XLA."""
    from repro.kernels.flash_attention import ops as flash_ops

    return flash_ops.flash_attention(q, k, v, causal=causal, window=window)


def _flash_attention_fwd(q, k, v, causal, window, chunk):
    return flash_attention(q, k, v, causal, window, chunk), (q, k, v)


def _flash_attention_bwd(causal, window, chunk, res, g):
    # Not a kernel, so no instruction name finds it in a profile: the
    # scope does.
    with jax.named_scope("attn.flash_bwd"):
        _, vjp = jax.vjp(
            functools.partial(
                chunked_attention, causal=causal, window=window, chunk=chunk
            ),
            *res,
        )
        return vjp(g)


flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int] = None):
    """Single-token attention against a (B, T, KV, D) cache.

    ``pos``: (B,) or scalar current position (cache entries > pos are
    invalid).  fp32 softmax; windowed masking for SWA/local attention.
    """
    B, T, KV, D = k_cache.shape
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)  # Sq == 1 squeezed
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache.astype(jnp.float32))
    s = s / math.sqrt(D)
    t = jnp.arange(T)
    pos = jnp.asarray(pos)
    pos_b = pos[:, None] if pos.ndim else pos[None, None]
    mask = t[None, :] <= pos_b
    if window is not None:
        mask = mask & (t[None, :] > pos_b - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


# -- full attention layer ------------------------------------------------------
def init_attention(key, cfg):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": init_dense(ks[0], d, cfg.q_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wk": init_dense(ks[1], d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wv": init_dense(ks[2], d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wo": init_dense(ks[3], cfg.q_dim, d, dtype=cfg.param_dtype),
    }
    return p


def _project_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(p["wk"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    cos, sin = make_rotary(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    return q, k, v


def attention_forward(p, x, cfg, *, window=None, causal=True, kv=None,
                      positions=None):
    """Training/prefill attention. kv: optional external (k, v) for
    cross-attention (enc-dec)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
    else:
        q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
        k, v = kv
        causal = False
    if cfg.use_pallas and jax.default_backend() == "tpu":
        out = flash_attention(q, k, v, causal, window, cfg.attn_chunk)
    else:
        out = chunked_attention(
            q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk
        )
    return dense(p["wo"], out.reshape(B, S, cfg.q_dim))


def attention_decode(p, x, cfg, cache, pos, *, window=None, cross_kv=None):
    """One-token decode. cache: {"k": (B,T,KV,D), "v": ...}; pos: (B,) or ().

    Returns (out, new_cache).  For cross-attention pass ``cross_kv`` and the
    (static) encoder KV is used without cache update.
    """
    B = x.shape[0]
    if cross_kv is not None:
        q = dense(p["wq"], x).reshape(B, 1, cfg.num_heads, cfg.head_dim)
        k_c, v_c = cross_kv
        T = k_c.shape[1]
        out = decode_attention(q, k_c, v_c, jnp.full((B,), T - 1), window=None)
        return dense(p["wo"], out.reshape(B, 1, cfg.q_dim)), cache
    positions = jnp.asarray(pos)
    positions = positions[:, None] if positions.ndim else jnp.full((B, 1), pos)
    q = dense(p["wq"], x).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    k = dense(p["wk"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    cos, sin = make_rotary(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    pos0 = positions[:, 0]
    k_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
        cache["k"], k, pos0
    )
    v_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
        cache["v"], v, pos0
    )
    out = decode_attention(q, k_cache, v_cache, pos0, window=window)
    return (
        dense(p["wo"], out.reshape(B, 1, cfg.q_dim)),
        {"k": k_cache, "v": v_cache},
    )
