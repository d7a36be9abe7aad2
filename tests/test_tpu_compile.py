"""Compile the main path's Pallas kernels for a TPU v5e 2x2 host.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a described topology with no chip attached.  It refuses what interpret
mode accepts: slices not aligned to the tiling, more VMEM than a kernel may
use, remote copies addressed to devices the mesh does not have.  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the one running this file loads
the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        t = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    if t is not None:
        yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    if t is None:
        pytest.skip(reason)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("x",))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel):
    """Compile ``fn``; its program holds the Pallas kernel under the
    instruction name ``kernel`` (``%<kernel>`` or ``%<kernel>.<n>``), the
    name a profile of the chip reports it by."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel not in program"
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call", text), \
        f"no custom call named {kernel!r}"
    return compiled


def test_flash_attention_compiles_at_smollm_widths(one_chip, monkeypatch):
    """smollm-360m: S=2048, 15 query heads over 5 KV heads, head_dim 64,
    bf16, causal; forward and the training gradient."""
    from repro.models.layers import flash_attention

    # The backend here is the CPU, on which the wrapper would pick
    # interpret mode; the program is compiled for the TPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    q = _spec((8, 2048, 15, 64), jnp.bfloat16, one_chip)
    kv = _spec((8, 2048, 5, 64), jnp.bfloat16, one_chip)

    def forward(q, k, v):
        return flash_attention(q, k, v, True, None, 512)

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).sum()

    # One kernel call per attention call: the tiling is one pallas_call.
    text = _compile(forward, q, kv, kv, kernel="flash_fwd").as_text()
    assert len(re.findall(r"%flash_fwd(\.\d+)? = .*custom-call", text)) == 1
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv,
             kernel="flash_fwd")


@pytest.mark.parametrize(
    "arch,Sq,Skv,causal",
    [
        ("recurrentgemma-9b", 4096, 4096, True),    # training, window 2048
        ("recurrentgemma-9b", 32768, 32768, True),  # 32k prefill
        ("mixtral-8x22b", 4096, 4096, True),        # training, window 4096
        ("mistral-large-123b", 4096, 4096, True),   # training
        ("whisper-medium", 448, 1500, False),       # cross-attention
    ],
)
def test_flash_forward_compiles_at_other_widths(one_chip, arch, Sq, Skv,
                                                causal):
    """The default tile plan fits the scoped VMEM for the registry's other
    attention shapes: every query head of the model on one chip, its
    window, bf16.  Whisper's decoder (448 positions) attends to the
    encoder's 1500 frames."""
    from repro.configs import get_config
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_pallas,
    )

    cfg = get_config(arch)
    window = cfg.sliding_window or cfg.local_window
    q = _spec((1, Sq, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, Skv, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16,
               one_chip)
    _compile(functools.partial(flash_attention_pallas, causal=causal,
                               window=window), q, kv, kv, kernel="flash_fwd")


def test_ssd_compiles_at_mamba2_370m_widths(one_chip):
    """mamba2-370m: 32 SSD heads of 64, state 128, one group, chunk 128."""
    from repro.configs import get_config
    from repro.kernels.ssd.ssd import ssd_scan_pallas

    cfg = get_config("mamba2-370m")
    B, S = 4, 2048
    H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    G = cfg.ssm_groups
    dt = jnp.dtype(cfg.dtype)
    args = (
        _spec((B, S, H, Pd), dt, one_chip),
        _spec((B, S, H), jnp.float32, one_chip),
        _spec((B, S, G, N), dt, one_chip),
        _spec((B, S, G, N), dt, one_chip),
    )
    _compile(functools.partial(ssd_scan_pallas, chunk=cfg.ssm_chunk), *args,
             kernel="ssd_scan")


def test_rg_lru_compiles_at_recurrentgemma_widths(one_chip):
    """recurrentgemma-9b: lru_width 4096, fp32 gates."""
    from repro.configs import get_config
    from repro.kernels.rg_lru.rg_lru import lru_scan_pallas

    w = get_config("recurrentgemma-9b").lru_width
    a = _spec((2, 2048, w), jnp.float32, one_chip)
    _compile(lru_scan_pallas, a, a, kernel="rg_lru_scan")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("kernel", ["allgather", "reduce_scatter"])
def test_ring_kernels_compile_on_four_chips(ring_mesh, kernel, dtype):
    """The RDMA ring kernels at a 4 MiB payload per chip, for each element
    width they move."""
    from repro.kernels.collectives.collectives import (
        device_ring_allgather,
        device_ring_reduce_scatter,
    )

    p, n = 4, (4 << 20) // jnp.dtype(dtype).itemsize
    if kernel == "allgather":
        def body(x):
            return device_ring_allgather(x, "x", p)[None]
        local = n
    else:
        def body(x):
            return device_ring_reduce_scatter(x.reshape(p, -1), "x", p)[None]
        local = n  # p contributions of n // p elements each

    fn = jax.shard_map(body, mesh=ring_mesh, in_specs=P("x"),
                       out_specs=P("x"), check_vma=False)
    x = _spec((p * local,), dtype, NamedSharding(ring_mesh, P("x")))
    _compile(fn, x, kernel=f"device_ring_{kernel}")
