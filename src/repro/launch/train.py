"""End-to-end training driver with burst-buffer checkpointing.

Wires together: config -> model -> optimizer -> jitted train step ->
synthetic data pipeline -> BBCheckpointManager (async save/flush) ->
restore from the burst buffer (``--restore``).

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
      --reduced --steps 50 --batch 8 --seq 64 --ckpt-every 10
``chip_smoke.py`` at the repo root drives the same functions at full width
on one TPU chip.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import serializer as ser
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.configs.base import get_config, reduced
from repro.core import BBConfig, BurstBufferSystem
from repro.data.pipeline import SyntheticLMPipeline
from repro.launch.jax_cache import use_compile_cache
from repro.models.registry import build_model
from repro.runtime.train_step import (TrainState, init_train_state,
                                      make_optimizer, make_train_step)

BB_SERVERS = 4
BB_REPLICATION = 2
BB_RETAINED = 2           # BBCheckpointManager's default retention
BB_MIN_DRAM = 256 << 20


def build(cfg, *, accum=1, peak_lr=3e-4):
    """Model, optimizer and the jitted train step. The step donates its
    state: at a chip's share of state the old and the new one do not both
    fit in device memory."""
    model = build_model(cfg)
    optimizer = make_optimizer(cfg, peak_lr=peak_lr)
    step_fn = jax.jit(make_train_step(cfg, model, optimizer,
                                      accum_steps=accum),
                      donate_argnums=(0,))
    return model, optimizer, step_fn


def make_pipeline(cfg, *, global_batch, seq_len):
    return SyntheticLMPipeline(
        vocab_size=cfg.vocab_size, seq_len=seq_len, global_batch=global_batch,
        enc_seq=cfg.encoder_seq, enc_dim=cfg.encoder_dim)


def ckpt_tree(state: TrainState, data_step):
    """What a checkpoint holds: the train state and the pipeline's step."""
    return {"params": state.params, "opt_state": state.opt_state,
            "data": {"step": jnp.asarray(data_step, jnp.int32)}}


def state_struct(model, optimizer, seed=0) -> TrainState:
    """The train state's shapes and dtypes, with nothing on a device."""
    params = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    return TrainState(params, jax.eval_shape(optimizer.init, params))


def bb_config_for(ckpt_bytes: int) -> BBConfig:
    """A burst buffer whose DRAM holds the retained checkpoints of
    ``ckpt_bytes`` with every replica, spread over its servers."""
    per_server = -(-BB_RETAINED * BB_REPLICATION * ckpt_bytes // BB_SERVERS)
    return BBConfig(num_servers=BB_SERVERS, num_clients=BB_SERVERS,
                    replication=BB_REPLICATION,
                    dram_capacity=max(per_server, BB_MIN_DRAM))


def restore_state(mgr: BBCheckpointManager, model, optimizer):
    """Restore the newest checkpoint into a new TrainState, built from
    shapes so that no second state is allocated first.

    Returns (state, data_step, ckpt_step); raises FileNotFoundError when
    the burst buffer holds no checkpoint."""
    struct = state_struct(model, optimizer)
    target = {"params": struct.params, "opt_state": struct.opt_state,
              "data": {"step": jax.ShapeDtypeStruct((), jnp.int32)}}
    restored, ck_step = mgr.restore(target)
    state = TrainState(restored["params"], restored["opt_state"])
    return state, int(restored["data"]["step"]), ck_step


def train_loop(cfg, *, steps, global_batch, seq_len, ckpt_every,
               bb_system=None, quantize_ckpt=True, accum=1, log_every=10,
               restore=False, seed=0):
    model, optimizer, step_fn = build(cfg, accum=accum)
    pipe = make_pipeline(cfg, global_batch=global_batch, seq_len=seq_len)

    own_bb = bb_system is None
    policy = ser.default_quant_policy if quantize_ckpt else None
    bb = bb_system or BurstBufferSystem(bb_config_for(ser.tree_nbytes(
        ckpt_tree(state_struct(model, optimizer, seed), 0), policy))).start()
    mgr = BBCheckpointManager(bb, quantize=quantize_ckpt)

    with contextlib.ExitStack() as cleanup:
        if own_bb:
            cleanup.callback(bb.stop)
        cleanup.callback(pipe.stop_prefetch)
        start_step = 0
        if restore:
            state, data_step, ck_step = restore_state(mgr, model, optimizer)
            pipe.load_state_dict({**pipe.state_dict(), "step": data_step})
            start_step = ck_step + 1
            print(f"[train] restored from step {ck_step}")
        else:
            state = init_train_state(cfg, model, optimizer,
                                     jax.random.PRNGKey(seed))
        pipe.start_prefetch()

        history = []
        t_last = time.perf_counter()
        for step in range(start_step, steps):
            batch = next(pipe)
            state, metrics = step_fn(state, batch)
            if ckpt_every and step and step % ckpt_every == 0:
                ingest = mgr.save(step, ckpt_tree(state, pipe.step))
                print(f"[ckpt] step {step}: ingest {ingest*1e3:.1f} ms "
                      f"({mgr.metrics[step]['bytes']/1e6:.1f} MB), "
                      f"flush async")
            if step % log_every == 0:
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t_last
                t_last = time.perf_counter()
                history.append((step, loss))
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} ({dt:.2f}s)")
        mgr.wait_flushes()
    return state, history, mgr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--restore", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    state, history, mgr = train_loop(
        cfg, steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        ckpt_every=args.ckpt_every, quantize_ckpt=not args.no_quant,
        accum=args.accum, restore=args.restore)
    print("final losses:", [f"{l:.4f}" for _, l in history[-5:]])
    print("ckpt metrics:", {k: v for k, v in sorted(mgr.metrics.items())})


if __name__ == "__main__":
    main()
