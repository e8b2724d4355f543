"""The training + checkpoint bring-up path on the CPU: ``launch/smoke.py``
at a reduced starcoder2-3b, the checks it makes, the pieces of
``launch/train.py`` and ``bbckpt.py`` it relies on, and ``chip_smoke.py``'s
refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import serializer as ser
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.configs.base import get_config, reduced
from repro.core import BBConfig, BurstBufferSystem
from repro.kernels import ops as kops
from repro.launch import jax_cache, smoke, train

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


def _cfg():
    return reduced(get_config("starcoder2-3b"))


def _run(code: str, *, env_extra=None, devices: int = 1, timeout=600):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)


# --------------------------------------------------------------- the smoke


def test_run_resume_reduced_starcoder2():
    """Three steps, quantized save into a live burst buffer, restore, and
    one more step with the uninterrupted step's loss."""
    report = smoke.run_resume(_cfg(), global_batch=2, seq_len=32,
                              log=lambda *_: None)
    assert report["quantized_leaves"] > 0 and report["exact_leaves"] > 0
    assert report["worst_int8_err_over_half_scale"] <= smoke.INT8_SLACK
    assert report["ckpt_bytes"] == ser.tree_nbytes(
        train.ckpt_tree(train.state_struct(*train.build(_cfg())[:2]), 0),
        ser.default_quant_policy)


def _saved_tree():
    rng = np.random.default_rng(0)
    return {"params": {"w": rng.normal(size=(8, 512)).astype(np.float32)},
            "opt_state": {"m": rng.normal(size=(8, 512)).astype(np.float32),
                          "step": np.int32(3)}}


def _restored_tree(saved):
    out = jax.tree.map(np.copy, saved)
    m = out["opt_state"]["m"]
    q, s = kops.quantize_blockwise(jnp.asarray(m.reshape(-1)))
    out["opt_state"]["m"] = np.array(
        kops.dequantize_blockwise(q, s)).reshape(m.shape)
    return out


def test_check_resume_accepts_quantized_restore():
    saved = _saved_tree()
    got = smoke.check_resume(saved, _restored_tree(saved),
                             quant_policy=ser.default_quant_policy,
                             loss_saved=1.5, loss_restored=1.5)
    assert (got["exact_leaves"], got["quantized_leaves"]) == (2, 1)


@pytest.mark.parametrize("fault", ["param_bit", "step", "moment", "loss"])
def test_check_resume_rejects(fault):
    saved = _saved_tree()
    restored = _restored_tree(saved)
    loss = 1.5
    if fault == "param_bit":
        restored["params"]["w"].view(np.uint32)[3, 7] ^= 1
    elif fault == "step":
        restored["opt_state"]["step"] = np.int32(2)
    elif fault == "moment":
        m = restored["opt_state"]["m"]
        scale = np.abs(saved["opt_state"]["m"]).reshape(-1, 2048).max(1)[0]
        m.reshape(-1)[5] = saved["opt_state"]["m"].reshape(-1)[5] \
            + 0.6 * scale / 127
    else:
        loss = np.nextafter(np.float32(1.5), np.float32(2))
    with pytest.raises(AssertionError):
        smoke.check_resume(saved, restored,
                           quant_policy=ser.default_quant_policy,
                           loss_saved=1.5, loss_restored=float(loss))


@pytest.mark.slow
def test_run_sharded_on_four_cpu_devices_subprocess():
    out = _run("""
        from repro.configs.base import get_config, reduced
        from repro.launch import smoke
        from repro.launch.mesh import make_host_mesh
        r = smoke.run_sharded(reduced(get_config("starcoder2-3b")),
                              make_host_mesh(data=2, model=2),
                              global_batch=2, seq_len=32)
        assert r["devices"] == 4 and r["quantized_leaves"] == 0, r
        print("SHARDED-OK")
    """, devices=4)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-OK" in out.stdout


@pytest.mark.slow
def test_flash_per_shard_matches_unsharded_subprocess():
    """The shard_map that runs the Pallas attention per shard on a mesh
    (kernels/ops._per_shard) gives the unsharded values and gradients."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.kernels import ops
        from repro.launch.mesh import make_host_mesh
        from repro.launch.sharding import RuleSet, use_rules

        rules = RuleSet(make_host_mesh(data=2, model=2))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (2, 64, 4, 32))
        k = jax.random.normal(ks[1], (2, 64, 2, 32))
        v = jax.random.normal(ks[2], (2, 64, 2, 32))
        w = jax.random.normal(ks[3], (2, 64, 4, 32))
        attn = lambda q, k, v: ops._flash_pallas(q, k, v, True, 0, 0.0, 0,
                                                 32, True)
        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * w)
        ref = jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)
        with use_rules(rules):
            got = jax.jit(jax.grad(loss(lambda q, k, v: ops._per_shard(
                attn, q, k, v)), argnums=(0, 1, 2)))(q, k, v)
            o = jax.jit(lambda q, k, v: ops._per_shard(attn, q, k, v))(
                q, k, v)
        np.testing.assert_allclose(o, attn(q, k, v), atol=1e-5)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)
        print("PER-SHARD-OK")
    """, devices=4)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PER-SHARD-OK" in out.stdout


def test_chip_smoke_refuses_cpu():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


# ------------------------------------------------------------ train.py


def test_restore_without_checkpoint_raises():
    with pytest.raises(FileNotFoundError):
        train.train_loop(_cfg(), steps=1, global_batch=2, seq_len=16,
                         ckpt_every=0, restore=True)


@pytest.mark.parametrize("quantize", [False, True])
def test_tree_nbytes_matches_serialized(quantize):
    model, optimizer, _ = train.build(_cfg())
    state = train.init_train_state(_cfg(), model, optimizer,
                                   jax.random.PRNGKey(0))
    tree = train.ckpt_tree(state, 7)
    policy = ser.default_quant_policy if quantize else None
    _, manifest = ser.serialize_tree(tree, policy)
    assert ser.tree_nbytes(tree, policy) == manifest["total_bytes"]
    assert ser.tree_nbytes(train.ckpt_tree(
        train.state_struct(model, optimizer), 7), policy) \
        == manifest["total_bytes"]


def test_bb_config_holds_retained_checkpoints_with_replicas():
    nbytes = 2_900_000_000
    cfg = train.bb_config_for(nbytes)
    assert cfg.num_servers * cfg.dram_capacity >= \
        2 * cfg.replication * nbytes
    assert train.bb_config_for(1000).dram_capacity == train.BB_MIN_DRAM


# --------------------------------------------------------- flush reporting


def _tiny_bb():
    return BurstBufferSystem(BBConfig(num_servers=2, num_clients=2,
                                      dram_capacity=16 << 20))


def test_wait_flushes_reports_running_flush(monkeypatch):
    release = threading.Event()
    with _tiny_bb() as bb:
        real_flush = bb.flush

        def slow_flush(epoch, timeout):
            release.wait(30)
            return real_flush(epoch, timeout)

        monkeypatch.setattr(bb, "flush", slow_flush)
        mgr = BBCheckpointManager(bb)
        mgr.save(4, {"w": np.arange(1024, dtype=np.float32)})
        with pytest.raises(TimeoutError, match="ckpt-flush-4"):
            mgr.wait_flushes(timeout=0.2)
        release.set()
        mgr.wait_flushes()                      # now finishes


def test_wait_flushes_reports_unacknowledged_flush(monkeypatch):
    with _tiny_bb() as bb:
        monkeypatch.setattr(bb, "flush", lambda epoch, timeout: False)
        mgr = BBCheckpointManager(bb)
        mgr.save(5, {"w": np.arange(1024, dtype=np.float32)})
        with pytest.raises(TimeoutError, match=r"steps \[5\]"):
            mgr.wait_flushes()
        with pytest.raises(TimeoutError, match="step 6"):
            mgr.save(6, {"w": np.arange(8, dtype=np.float32)},
                     blocking_flush=True)


# ---------------------------------------------------------- compile cache


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = jax_cache.use_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert got == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "jaxcache"
    out = _run("""
        import os, jax, jax.numpy as jnp
        from repro.launch.jax_cache import use_compile_cache
        print(use_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """, env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache),
                    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [str(cache)]
    assert any(cache.iterdir())
