"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the widths the chip runs (starcoder2-3b leaves and
attention; rg_lru at batch 2). The TPU compiler refuses tilings that
interpret mode accepts, so these guard the chip path without a chip.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.launch.sharding import RuleSet, use_rules
from repro.kernels.quantize import (dequantize_blockwise_pallas,
                                    quantize_blockwise_pallas)
from repro.kernels.rg_lru import rg_lru_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


# starcoder2-3b leaves: attn/mlp matrix, kv projection, tied embedding
QUANT_LEAVES = [(3072, 12288), (3072, 256), (49152, 3072)]


@pytest.mark.parametrize("shape", QUANT_LEAVES)
def test_quantize_compiles_for_v5e(one_chip, shape):
    n = shape[0] * shape[1]
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    _compile(quantize_blockwise_pallas, x)


@pytest.mark.parametrize("shape", QUANT_LEAVES)
def test_dequantize_compiles_for_v5e(one_chip, shape):
    n = shape[0] * shape[1]
    q = jax.ShapeDtypeStruct((n,), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((n // 2048,), jnp.float32, sharding=one_chip)
    _compile(dequantize_blockwise_pallas, q, s)


def test_quantize_compiles_for_ragged_block_count(one_chip):
    x = jax.ShapeDtypeStruct((100 * 2048,), jnp.float32, sharding=one_chip)
    _compile(quantize_blockwise_pallas, x)


# deepseek-llm-7b.2l moments: embedding, stacked MLP matrix, stacked norms
SAVE_LEAVES = [(25600, 4096), (2, 4096, 11008), (2, 4096)]


@pytest.mark.parametrize("shape", SAVE_LEAVES)
def test_leaf_quantize_compiles_for_v5e(one_chip, shape, monkeypatch):
    """A save's flatten, pad and kernel on the device, one program a leaf
    shape (shapes no CPU test traces: the jitted trace is kept by shape)."""
    from repro.checkpoint import serializer as ser
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = ser._quantize_leaf.lower(x, block=ser.QUANT_BLOCK).compile()
    assert "tpu_custom_call" in hlo.as_text()


def _attn_args(one_chip, seq=4096):
    q = jax.ShapeDtypeStruct((1, seq, 24, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    return q, kv, kv


def _flash(q, k, v):
    return ops._flash_pallas(q, k, v, True, 0, 0.0, 0, 512, False)


def test_flash_forward_compiles_for_v5e(one_chip):
    _compile(_flash, *_attn_args(one_chip))


def test_flash_backward_compiles_for_v5e(one_chip):
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_attn_args(one_chip))


def test_flash_backward_compiles_sharded_on_v5e_2x2(mesh_2x2):
    """On a mesh the kernel runs per shard (ops._per_shard): XLA refuses
    to partition a Mosaic kernel by itself."""
    replicated = NamedSharding(mesh_2x2, P())
    q, k, v = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
               for a in _attn_args(None))

    def loss(q, k, v):
        o = ops._per_shard(_flash, q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    with use_rules(RuleSet(mesh_2x2)):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_rg_lru_compiles_for_v5e_batch2(one_chip):
    a = jax.ShapeDtypeStruct((2, 4096, 2560), jnp.bfloat16, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((2, 2560), jnp.bfloat16, sharding=one_chip)
    _compile(rg_lru_pallas, a, a, h0)
