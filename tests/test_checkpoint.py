"""Checkpoint serializer + BBCheckpointManager: round-trips, quantization
error bounds, what a save fetches to the host, restore fast paths, replica
failover restore."""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint import serializer as ser
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.core import BBConfig, BurstBufferSystem
from repro.kernels import ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tree(seed=0, dtype=jnp.float32):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "params": {"w": jax.random.normal(ks[0], (64, 32), dtype),
                   "b": jax.random.normal(ks[1], (32,), dtype)},
        "opt_state": {"m": jax.random.normal(ks[2], (64, 32), dtype),
                      "step": jnp.asarray(7, jnp.int32)},
        "data": {"step": jnp.asarray(13, jnp.int32)},
    }


def test_serialize_roundtrip_bit_exact_f32():
    tree = _tree()
    payloads, manifest = ser.serialize_tree(tree)
    out = ser.deserialize_tree(tree, payloads, manifest)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serialize_roundtrip_bf16():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (33, 17),
                                   jnp.bfloat16)}
    payloads, manifest = ser.serialize_tree(tree)
    out = ser.deserialize_tree(tree, payloads, manifest)
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["w"], np.float32), np.asarray(tree["w"], np.float32))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_quantized_moments_error_bound(seed):
    rng = np.random.default_rng(seed)
    leaf = jnp.asarray(rng.normal(0, 0.02, (64, 64)), jnp.float32)
    tree = {"opt_state": {"m": leaf}}
    payloads, manifest = ser.serialize_tree(tree, ser.default_quant_policy)
    assert manifest["leaves"][0]["quant"]
    out = ser.deserialize_tree(tree, payloads, manifest)
    err = np.abs(np.asarray(out["opt_state"]["m"]) - np.asarray(leaf))
    # blockwise int8: |err| <= max|block| / 254 + eps
    assert err.max() <= np.abs(np.asarray(leaf)).max() / 127 + 1e-6


def _moments_tree():
    """Two int8 moments (one a whole number of blocks, one padded) beside
    exact leaves."""
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    return {"params": {"w": jax.random.normal(k[0], (64, 32), jnp.bfloat16)},
            "opt_state": {
                "m": 0.02 * jax.random.normal(k[1], (64, 32), jnp.float32),
                "v": 0.02 * jax.random.normal(k[2], (3, 1000), jnp.float32),
                "step": jnp.asarray(7, jnp.int32)}}


def test_save_fetches_only_the_checkpoint(monkeypatch):
    """An int8 leaf crosses the host link as its int8 values (four to a
    32-bit word) and f32 block scales, never at full precision: a save
    fetches its payload bytes."""
    tree = _moments_tree()
    int8 = {n: l for n, l in ser.tree_paths(tree)
            if ser.default_quant_policy(n, l)}
    assert sorted(int8) == ["opt_state/m", "opt_state/v"]
    fetched = []
    device_get = jax.device_get

    def spy(x):
        out = device_get(x)
        fetched.extend(np.asarray(a) for a in jax.tree.leaves(out))
        return out

    monkeypatch.setattr(jax, "device_get", spy)
    _, manifest = ser.serialize_tree(tree, ser.default_quant_policy)
    blocks = {-(-l.size // ser.QUANT_BLOCK) for l in int8.values()}
    for a in fetched:
        assert not any(a.shape == l.shape and a.dtype == l.dtype
                       for l in int8.values()), a.shape
        if a.dtype == np.float32:
            assert a.ndim == 1 and a.size in blocks, a.shape
    values = [a for a in fetched if a.dtype == np.uint32]
    assert sorted(a.shape for a in values) == sorted(
        (b, ser.QUANT_BLOCK // 4) for b in blocks)
    assert sum(a.nbytes for a in fetched) == manifest["total_bytes"]


@pytest.mark.parametrize("shape", [(64, 32), (3, 1000)])
def test_quantized_payload_is_the_reference_on_the_host_copy(shape):
    """The payload is, byte for byte, the reference quantization of the
    zero-padded f32 host copy: values, then scales."""
    leaf = jnp.asarray(np.random.default_rng(5).normal(0, 0.02, shape),
                       jnp.float32)
    host = np.asarray(leaf, np.float32).reshape(-1)
    pad = (-host.size) % ser.QUANT_BLOCK
    q, s = ref.quantize_blockwise(jnp.asarray(np.pad(host, (0, pad))),
                                  ser.QUANT_BLOCK)
    payload, meta = ser.serialize_leaf(leaf, True, "opt_state/m")
    assert payload == np.asarray(q).tobytes() + \
        np.asarray(s, np.float32).tobytes()
    assert meta == {"shape": list(shape), "dtype": "float32", "quant": True,
                    "pad": pad, "nq": host.size + pad,
                    "block": ser.QUANT_BLOCK}
    out = ser.deserialize_leaf(payload, meta)
    assert out.shape == shape and out.dtype == np.float32
    # blockwise int8: |err| <= max|block| / 254 + eps
    assert np.abs(out - np.asarray(leaf)).max() <= \
        np.abs(host).max() / 127 + 1e-6


@pytest.mark.slow
def test_sharded_moment_serializes_as_its_host_copy_subprocess():
    """A moment sharded over four devices is quantized on one of them: its
    payload is the one its host copy gives."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import serializer as ser
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("d", "m"))
        host = np.random.default_rng(6).normal(0, 0.02, (48, 1000))
        host = host.astype(np.float32)
        leaf = jax.device_put(host, NamedSharding(mesh, P("d", "m")))
        assert len(leaf.sharding.device_set) == 4
        assert ser.serialize_leaf(leaf, True) == \
            ser.serialize_leaf(host, True)
        print("SHARDED-OK")
    """
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-OK" in out.stdout


def test_manager_save_restore_roundtrip():
    with BurstBufferSystem(BBConfig(num_servers=4, num_clients=4,
                                    dram_capacity=64 << 20)) as bb:
        mgr = BBCheckpointManager(bb, quantize=False)
        tree = _tree(1)
        mgr.save(5, tree, blocking_flush=True)
        restored, step = mgr.restore(_tree(99))
        assert step == 5
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_latest_of_many_and_retention():
    with BurstBufferSystem(BBConfig(num_servers=4, num_clients=4,
                                    dram_capacity=64 << 20)) as bb:
        mgr = BBCheckpointManager(bb, quantize=False, retention=2)
        for step in (1, 2, 3):
            mgr.save(step, _tree(step), blocking_flush=True)
        assert sorted(mgr.saved_steps) == [2, 3]     # retention evicted 1
        restored, step = mgr.restore(_tree(0))
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]),
            np.asarray(_tree(3)["params"]["w"]))


def test_restore_from_pfs_after_eviction():
    """Evicted epochs are durably on the PFS; restore falls back there."""
    with BurstBufferSystem(BBConfig(num_servers=4, num_clients=4,
                                    dram_capacity=64 << 20)) as bb:
        mgr = BBCheckpointManager(bb, quantize=False, retention=1)
        mgr.save(1, _tree(1), blocking_flush=True)
        mgr.save(2, _tree(2), blocking_flush=True)
        restored, step = mgr.restore(_tree(0), step=1)   # evicted from BB
        assert step == 1
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]),
            np.asarray(_tree(1)["params"]["w"]))


def test_restore_survives_server_failure():
    """Kill a server after save: replicas must still reconstruct the full
    checkpoint (paper §IV-B data recovery)."""
    with BurstBufferSystem(BBConfig(num_servers=4, num_clients=4,
                                    dram_capacity=64 << 20,
                                    stabilize_interval=0.1)) as bb:
        mgr = BBCheckpointManager(bb, quantize=False)
        tree = _tree(2)
        mgr.save(9, tree, blocking_flush=True)
        bb.kill_server("server/1")
        time.sleep(1.0)               # stabilization + client updates
        for c in bb.clients:
            c.put_timeout = 0.8
        restored, step = mgr.restore(_tree(0))
        assert step == 9
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
