"""Bring-up checks of the training + burst-buffer checkpoint path.

``run_resume`` trains a few steps through ``launch/train.py``, saves the
train state into a running ``BurstBufferSystem``, restores it and resumes.
``run_sharded`` does the same for a state sharded over a mesh, restored
with ``launch/elastic.elastic_restore``. ``chip_smoke.py`` runs them at full
width on TPU chips; the tests run them at a reduced config on the CPU. Every
check raises on failure, and nothing here catches an exception.

Times printed here come from one run each and are not a benchmark.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import serializer as ser
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.configs.base import get_config
from repro.core import BurstBufferSystem
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.launch import train
from repro.launch.elastic import elastic_restore
from repro.launch.sharding import RuleSet, batch_axes, use_rules
from repro.runtime.train_step import (TrainState, init_train_state,
                                      make_train_step, state_logical_axes)

SMOKE_ARCH = "starcoder2-3b"
SMOKE_LAYERS = 6          # of 30; the layer pattern is all "attn"
# sharded vs one-device loss: only the order of reductions differs, but a
# random-init network amplifies it (9.1e-4 on a v5e 2x2 at 6 layers)
LOSS_RTOL_SHARDED = 1e-3
# |err| <= scale / 2, plus f32 rounding of x / scale and q * scale
INT8_SLACK = 1 + 2e-4
# attention vs the oracle, relative to the oracle's largest magnitude: the
# kernel's f32 dots may run as bf16 passes, the oracle runs at "highest"
ATTN_RTOL = 2e-2


def smoke_config():
    """starcoder2-3b at its published widths with only the depth cut."""
    cfg = get_config(SMOKE_ARCH)
    return dataclasses.replace(cfg, segments=((("attn",), SMOKE_LAYERS),))


def _flat_bytes(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x).reshape(-1).view(np.uint8)


def int8_error_ratio(saved: np.ndarray, restored: np.ndarray) -> float:
    """Largest |restored - saved| over half the blockwise int8 scale
    (max |block| / 127, as ``kernels/quantize.py`` computes it)."""
    block = ser.QUANT_BLOCK
    x = saved.astype(np.float32).reshape(-1)
    y = restored.astype(np.float32).reshape(-1)
    pad = (-x.size) % block
    x = np.pad(x, (0, pad)).reshape(-1, block)
    y = np.pad(y, (0, pad)).reshape(-1, block)
    scale = np.maximum(np.abs(x).max(axis=1, keepdims=True) / np.float32(127),
                       np.float32(1e-12))
    return float((np.abs(y - x) / (scale / 2)).max())


def check_resume(saved, restored, *, quant_policy, loss_saved,
                 loss_restored) -> dict:
    """Hold a restored checkpoint to the in-memory state it was saved from.

    ``saved`` and ``restored`` are host trees of one structure. A leaf that
    ``quant_policy`` quantizes (an optimizer moment) must lie within its
    blockwise int8 bound; every other leaf (params, step counters) must be
    byte-exact. The step after the restore must give the same loss as the
    same step from the in-memory state."""
    s_leaves = dict(ser.tree_paths(saved))
    r_leaves = dict(ser.tree_paths(restored))
    assert s_leaves.keys() == r_leaves.keys(), \
        sorted(s_leaves.keys() ^ r_leaves.keys())
    exact, quantized, worst = 0, 0, 0.0
    for name, x in s_leaves.items():
        x, y = np.asarray(x), np.asarray(r_leaves[name])
        assert (x.shape, x.dtype) == (y.shape, y.dtype), \
            (name, x.shape, x.dtype, y.shape, y.dtype)
        if quant_policy is not None and quant_policy(name, x):
            ratio = int8_error_ratio(x, y)
            assert ratio <= INT8_SLACK, \
                f"{name}: error {ratio} x scale/2 exceeds the int8 bound"
            worst = max(worst, ratio)
            quantized += 1
        else:
            assert np.array_equal(_flat_bytes(x), _flat_bytes(y)), \
                f"{name}: restored bytes differ from the saved state"
            exact += 1
    assert loss_restored == loss_saved, \
        f"loss after resume {loss_restored!r} != in-memory {loss_saved!r}"
    return {"exact_leaves": exact, "quantized_leaves": quantized,
            "worst_int8_err_over_half_scale": worst}


def check_attention(cfg, *, seq_len=512, log=print) -> float:
    """The attention the train step runs (on TPU the Pallas kernel and its
    flash backward) against the naive oracle in ``kernels/ref.py``, at the
    config's head widths on a small input: the output and the q/k/v
    gradients. Returns the worst error over the oracle's largest value."""
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    shapes = [(1, seq_len, h, d), (1, seq_len, kvh, d), (1, seq_len, kvh, d),
              (1, seq_len, h, d)]
    args = [jax.random.normal(key, shape) for key, shape in
            zip(jax.random.split(jax.random.PRNGKey(1), 4), shapes)]

    @functools.partial(jax.jit, static_argnums=0)
    def out_and_grads(attn, q, k, v, cotangent):
        out, pull = jax.vjp(attn, q, k, v)
        return (out, *pull(cotangent))

    got = out_and_grads(kops.flash_attention, *args)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(ref.flash_attention, *args)
    worst = max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
                for g, w in zip(got, want))
    assert worst <= ATTN_RTOL, f"attention off the oracle by {worst}"
    log(f"[smoke] attention fwd+bwd at seq {seq_len} vs the oracle: worst "
        f"error {worst!r} of the oracle's largest value")
    return worst


def _require_kernels(jitted, *args, what: str, log) -> float:
    """On TPU, compile ``jitted`` for ``args`` and require a Pallas kernel
    in its HLO. Returns the compile seconds (0 off the TPU, where the jnp
    paths run instead)."""
    if jax.default_backend() != "tpu":
        return 0.0
    t0 = time.perf_counter()
    hlo = jitted.lower(*args).compile().as_text()
    dt = time.perf_counter() - t0
    assert "tpu_custom_call" in hlo, f"{what}: no Pallas kernel in the HLO"
    log(f"[smoke] {what}: compiled in {dt:.2f} s, Pallas kernel present")
    return dt


def _batch_struct(global_batch, seq_len):
    return {k: jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
            for k in ("inputs", "labels")}


def _loss(step_fn, state, batch) -> float:
    """One step's loss. The step donates ``state``; its result is dropped
    at once so that only one state is on the device."""
    new_state, metrics = step_fn(state, batch)
    loss = float(metrics["loss"])
    del new_state
    return loss


def run_resume(cfg, *, global_batch, seq_len, steps=3, log=print) -> dict:
    """Train ``steps`` steps through ``train.train_loop`` with a quantized
    save at the last one, restore into a new state, check it and resume."""
    model, optimizer, step_fn = train.build(cfg)
    policy = ser.default_quant_policy
    struct = train.state_struct(model, optimizer)
    big = max(leaf.size for leaf in jax.tree.leaves(struct.opt_state))
    big = -(-big // ser.QUANT_BLOCK) * ser.QUANT_BLOCK
    report = {
        "attention_err": check_attention(cfg, log=log),
        "compile_train_step_s": _require_kernels(
            step_fn, struct, _batch_struct(global_batch, seq_len),
            what="train step", log=log),
        "compile_quantize_s": _require_kernels(
            jax.jit(kops.quantize_blockwise),
            jax.ShapeDtypeStruct((big,), jnp.float32),
            what="quantize", log=log)}

    saved_step = steps - 1
    bb = BurstBufferSystem(train.bb_config_for(ser.tree_nbytes(
        train.ckpt_tree(struct, 0), policy))).start()
    try:
        t0 = time.perf_counter()
        state, _, mgr = train.train_loop(
            cfg, steps=steps, global_batch=global_batch, seq_len=seq_len,
            ckpt_every=saved_step, bb_system=bb, quantize_ckpt=True,
            log_every=1)
        report["train_and_save_s"] = time.perf_counter() - t0
        assert mgr.saved_steps == [saved_step], mgr.saved_steps
        m = mgr.metrics[saved_step]
        report.update(ckpt_bytes=m["bytes"], ingest_s=m["ingest_s"],
                      flush_s=m["flush_s"])
        log(f"[smoke] checkpoint of step {saved_step}: {m['bytes']} bytes, "
            f"ingest {m['ingest_s']:.2f} s, flush {m['flush_s']:.2f} s")

        saved = jax.device_get(train.ckpt_tree(state, steps))
        pipe = train.make_pipeline(cfg, global_batch=global_batch,
                                   seq_len=seq_len)
        pipe.load_state_dict({**pipe.state_dict(), "step": steps})
        batch = next(pipe)
        loss_saved = _loss(step_fn, state, batch)

        t0 = time.perf_counter()
        restored, data_step, ck_step = train.restore_state(mgr, model,
                                                           optimizer)
        restored_host = jax.device_get(train.ckpt_tree(restored, data_step))
        report["restore_s"] = time.perf_counter() - t0
        log(f"[smoke] restored step {ck_step} in {report['restore_s']:.2f} s")
        assert (ck_step, data_step) == (saved_step, steps), (ck_step,
                                                             data_step)
    finally:
        bb.stop()

    loss_restored = _loss(step_fn, restored, batch)
    report.update(check_resume(saved, restored_host, quant_policy=policy,
                               loss_saved=loss_saved,
                               loss_restored=loss_restored),
                  loss_after_resume=loss_restored)
    log(f"[smoke] resumed step {steps}: loss {loss_restored!r}, equal to the "
        f"uninterrupted step's")
    return report


def run_sharded(cfg, mesh, *, global_batch, seq_len, log=print) -> dict:
    """A train step sharded over ``mesh`` against the same step on one
    device; then save the sharded state and restore it onto ``mesh``."""
    model, optimizer, step_one = train.build(cfg)
    rules = RuleSet(mesh)
    struct = train.state_struct(model, optimizer)
    shardings = rules.tree_shardings(
        state_logical_axes(cfg, model, optimizer), struct)
    pipe = train.make_pipeline(cfg, global_batch=global_batch,
                               seq_len=seq_len)
    batches = [next(pipe), next(pipe)]
    b_sh = rules.tree_shardings(batch_axes(batches[0]), batches[0])
    step_sharded = jax.jit(make_train_step(cfg, model, optimizer),
                           in_shardings=(shardings, b_sh),
                           out_shardings=(shardings, None),
                           donate_argnums=(0,))
    with use_rules(rules):
        _require_kernels(step_sharded, struct,
                         _batch_struct(global_batch, seq_len),
                         what="sharded train step", log=log)

    state = init_train_state(cfg, model, optimizer, jax.random.PRNGKey(0))
    host = jax.device_get(state)
    loss_one = _loss(step_one, state, batches[0])
    state = jax.device_put(host, shardings)
    del host
    with use_rules(rules):
        state, metrics = step_sharded(state, batches[0])
    loss_mesh = float(metrics["loss"])
    log(f"[smoke] step 0 loss: one device {loss_one!r}, mesh "
        f"{dict(mesh.shape)} {loss_mesh!r}")
    assert abs(loss_mesh - loss_one) <= LOSS_RTOL_SHARDED * abs(loss_one), \
        (loss_mesh, loss_one)

    ckpt = train.ckpt_tree(state, 1)
    bb = BurstBufferSystem(train.bb_config_for(ser.tree_nbytes(ckpt))
                           ).start()
    try:
        mgr = BBCheckpointManager(bb, quantize=False)
        saved = jax.device_get({"params": state.params,
                                "opt_state": state.opt_state})
        mgr.save(1, ckpt)
        mgr.wait_flushes()
        log(f"[smoke] saved sharded state: {mgr.metrics[1]['bytes']} bytes")
        with use_rules(rules):
            loss_saved = _loss(step_sharded, state, batches[1])
        t0 = time.perf_counter()
        restored, ck_step = elastic_restore(
            mgr, cfg, model, optimizer, mesh,
            {"params": struct.params, "opt_state": struct.opt_state})
        log(f"[smoke] elastic restore onto the mesh: "
            f"{time.perf_counter() - t0:.2f} s")
        assert ck_step == 1, ck_step
    finally:
        bb.stop()

    devices = set(mesh.devices.flat)
    targets = dict(ser.tree_paths({"params": shardings.params,
                                   "opt_state": shardings.opt_state}))
    for name, leaf in ser.tree_paths(restored):
        assert leaf.sharding.is_equivalent_to(targets[name], leaf.ndim), \
            (name, leaf.sharding, targets[name])
        assert leaf.sharding.device_set == devices, (name, leaf.sharding)
    restored_host = jax.device_get(restored)
    with use_rules(rules):
        loss_restored = _loss(
            step_sharded,
            TrainState(restored["params"], restored["opt_state"]),
            batches[1])
    report = check_resume(saved, restored_host, quant_policy=None,
                          loss_saved=loss_saved, loss_restored=loss_restored)
    log(f"[smoke] {len(targets)} leaves restored in their target shardings "
        f"over {len(devices)} devices, bytes equal; resumed loss "
        f"{loss_restored!r}")
    report.update(loss_one_device=loss_one, loss_mesh=loss_mesh,
                  devices=len(devices))
    return report
