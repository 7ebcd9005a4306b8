"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for a described (not attached)
v5e:2x2 topology and compiles it with the TPU compiler, which refuses what
interpret mode accepts (blocks that break the (8, 128) tiling rule, kernels
that overflow VMEM, kernels left to the automatic partitioner). Each test
asserts the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import kernel_mesh
from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_decode import ops as dops
from repro.kernels.gram import ops as gops
from repro.kernels.wkv6 import ops as wops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gram_deit_base(one_chip, dtype):
    """CORP's calibration second moment at deit-base's MLP tap: 8 images x
    197 tokens by d_ff 3072."""
    x = sds((1576, 3072), dtype, one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda x: gops.gram(x, impl="pallas"), x)


def test_gram_cross_shard_local(one_chip):
    """One shard's (N, F) x (N, F/4) slab of the model-sharded gram."""
    x = sds((1576, 3072), "float32", one_chip)
    y = sds((1576, 768), "float32", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda x, y: gops.gram_cross(x, y, impl="pallas"), x, y)


@pytest.mark.parametrize("dq", [64, 32])
def test_flash_attention_deit_base(one_chip, dq):
    """deit-base attention (197 tokens, 12 heads), dense and CORP-pruned
    qk (dq 32); 197 pads to two 128-row blocks."""
    q = sds((8, 197, 12, dq), "float32", one_chip)
    v = sds((8, 197, 12, 64), "float32", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda q, k, v: fops.attention(q, k, v, causal=False, impl="pallas"),
        q, q, v)


def test_flash_attention_causal_unaligned(one_chip):
    """Causal GQA prefill at 3000 tokens: one whole-length block overflowed
    VMEM; padding to 128-row blocks fits."""
    q = sds((1, 3000, 12, 128), "bfloat16", one_chip)
    kv = sds((1, 3000, 2, 128), "bfloat16", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda q, k, v: fops.attention(q, k, v, causal=True, impl="pallas"),
        q, kv, kv)


def test_flash_decode_qwen2(one_chip):
    """qwen2-1.5b decode over a 1024-row slot cache (12 q / 2 kv heads)."""
    q = sds((8, 12, 128), "bfloat16", one_chip)
    kv = sds((8, 1024, 2, 128), "bfloat16", one_chip)
    valid = sds((8, 1024), "bool", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda q, k, v, m: dops.decode_attention(q, k, v, m, impl="pallas"),
        q, kv, kv, valid)


@pytest.mark.parametrize("T", [1, 512])
def test_wkv6_rwkv6_3b(one_chip, T):
    """rwkv6-3b's recurrence (40 heads of 64): decode and a 512-token
    prefill in chunks of 64."""
    x = sds((1, T, 40, 64), "bfloat16", one_chip)
    u = sds((40, 64), "float32", one_chip)
    assert "tpu_custom_call" in compiled_text(
        lambda r, k, v, w, u: wops.wkv6(r, k, v, w, u, impl="pallas"),
        x, x, x, x, u)


def test_kernels_per_device_on_four_chips(mesh):
    """Inside a program that spans four chips (deepseek-7b decode and
    prefill, 32 heads split over the model axis) the kernels only compile
    as per-device programs: ``kernel_mesh`` wraps them in a shard_map."""
    heads = NamedSharding(mesh, P(None, None, "model", None))
    q = sds((4, 32, 128), "bfloat16", NamedSharding(mesh, P(None, "model")))
    kv = sds((4, 1024, 32, 128), "bfloat16", heads)
    valid = sds((4, 1024), "bool", NamedSharding(mesh, P()))
    seq = sds((1, 200, 32, 128), "bfloat16", heads)

    def step(q, k, v, m, s):
        with kernel_mesh(mesh):
            return (dops.decode_attention(q, k, v, m, impl="pallas"),
                    fops.attention(s, s, s, causal=True, impl="pallas"))
    assert "tpu_custom_call" in compiled_text(step, q, kv, kv, valid, seq)
