#!/usr/bin/env python3
"""Bring-up smoke of the training + burst-buffer checkpoint path on TPU.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # sharded save/restore on a 2x2 mesh

One chip: starcoder2-3b at its published widths with the depth cut to 6 of
its 30 layers, batch 1 x seq 4096 (one chip's share of train_4k). Three
steps through ``launch/train.py``, a quantized save at the last one through
``BBCheckpointManager`` into a running ``BurstBufferSystem``, a restore into
a new state, and one more step whose loss must equal the same step's from
the in-memory state (``repro.launch.smoke.run_resume``).

``--chips 4`` runs only the sharded path and what it is compared with: the
same train step sharded over (data=2, model=2) against the one-chip step,
then a save of the sharded state and a restore onto the mesh with
``elastic_restore`` (``repro.launch.smoke.run_sharded``).

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU
the script exits non-zero before any work; a failed check ends it with a
traceback and a non-zero exit. All work runs in this one process.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH, SEQ = 1, 4096


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices; JAX found {len(devices)}")

    from repro.configs.base import get_config
    from repro.launch import smoke
    from repro.launch.jax_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import count_params

    print(f"[smoke] compile cache: {use_compile_cache()}")
    cfg = smoke.smoke_config()
    print(f"[smoke] {cfg.name}: d_model {cfg.d_model}, {cfg.num_heads} heads "
          f"({cfg.num_kv_heads} KV, head_dim {cfg.resolved_head_dim}), "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; depth cut to "
          f"{cfg.num_layers} of {get_config(smoke.SMOKE_ARCH).num_layers} "
          f"layers = {count_params(cfg) / 1e9:.3f} B params; batch {BATCH} x "
          f"seq {SEQ}; {len(devices)} x {dev.device_kind}")
    if args.chips == 4:
        smoke.run_sharded(cfg, make_host_mesh(data=2, model=2),
                          global_batch=BATCH, seq_len=SEQ)
    else:
        smoke.run_resume(cfg, global_batch=BATCH, seq_len=SEQ)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
