"""Readings that the limits in ``bench/limits/`` are set from, for one
configuration, in one process on the chip.

For each seed the program runs its first steps from the seed's weights (the
window's own call), and the plain reference follows them: each gap that a
cell's check compares is read. On the first ``--controls`` seeds the same is
read with the reference put in the program's place twice: computed in the
precision below the configuration's (the control), and with half of each
batch's tokens left out of the mean (a fault). The checkpoint's int8 bound
is read on every seed from the program's own quantize and dequantize kernels
at the state's leaf sizes, and, for the control, from int4 blocks.

    python bench/calibrate.py --config deepseek-llm-7b.2l --mix save60 \\
        --seeds 12 --controls 3 --out calib.json
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the precision below each one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
FIRST_SEED = 1_000_003


CALIB_BLOCKS = 256        # whole blocks of each int8 leaf read per seed


def int4_round(x):
    """``x`` rounded as blockwise int4 would keep it (a scale per block of
    QUANT_BLOCK values, 15 levels): the control of the int8 guarantee."""
    import jax.numpy as jnp
    from bench.harness import QUANT_BLOCK
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % QUANT_BLOCK
    xb = jnp.pad(flat, (0, pad)).reshape(-1, QUANT_BLOCK)
    s = jnp.maximum(jnp.abs(xb).max(1, keepdims=True) / 7, 1e-12)
    y = (jnp.clip(jnp.round(xb / s), -7, 7) * s).reshape(-1)
    return y[:flat.shape[0]].reshape(x.shape).astype(x.dtype)


def int8_bound_readings(tree, seed):
    """(program, control): the worst restored error over its int8 bound
    (``harness.int8_err``, the check's own) of any leaf that may be saved
    as int8, through the program's quantize and dequantize kernels as the
    serializer runs them, and through int4; read on CALIB_BLOCKS whole
    blocks of each leaf drawn from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import (QUANT_BLOCK, _rng, int8_allowed, int8_err,
                               leaf_items)
    from repro.kernels import ops as kops

    worst, worst4 = 0.0, 0.0
    for name, leaf in leaf_items(tree):
        if not int8_allowed(name, leaf):
            continue
        flat = leaf.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % QUANT_BLOCK
        q, s = kops.quantize_blockwise(jnp.pad(flat, (0, pad)),
                                       block=QUANT_BLOCK)
        y = kops.dequantize_blockwise(q, s, block=QUANT_BLOCK)
        y = y[:flat.shape[0]].astype(leaf.dtype)
        nb = flat.shape[0] // QUANT_BLOCK
        blocks = np.sort(_rng(seed, name).choice(
            nb, min(nb, CALIB_BLOCKS), replace=False))
        idx = jnp.asarray((blocks[:, None] * QUANT_BLOCK
                           + np.arange(QUANT_BLOCK)[None]).reshape(-1))
        x_s, y_s, i4_s = jax.device_get((leaf.reshape(-1)[idx], y[idx],
                                         int4_round(leaf).reshape(-1)[idx]))
        worst = max(worst, int8_err(x_s, y_s))
        worst4 = max(worst4, int8_err(x_s, i4_s))
    return worst, worst4


def calibrate(cfg, mix, seeds, controls, log=print):
    import jax
    from bench import harness, reference
    from repro.launch import train
    if jax.devices()[0].platform != "tpu":
        raise harness.NoChip("calibration reads the chip")
    program = harness.Program(cfg, harness.Hooks())
    low = LOWER[cfg["training"]["compute_dtype"]]
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        feed = harness.Feed(seed, cfg, mix)
        state, prog = program.first_steps(seed, feed)
        i8, i4 = int8_bound_readings(train.ckpt_tree(state, feed.index),
                                      seed)
        del state
        batches = [feed.host(k) for k in range(harness.REF_STEPS)]
        ref = reference.train(cfg, seed, batches, steps=harness.REF_STEPS)
        row = {"seed": seed, "program": harness.training_numbers(prog, ref),
               "ckpt_int8_err": i8, "ckpt_int8_err_int4": i4,
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        if i < controls:
            for what, kw in (("control", {"low": low}),
                             ("half_batch", {"half": True})):
                alt = reference.train(cfg, seed, batches,
                                      steps=harness.REF_STEPS, **kw)
                alt["resumed"] = []
                row[what] = harness.training_numbers(alt, ref)
        row["seconds"] = time.perf_counter() - t0
        log(json.dumps(row))
        rows.append(row)
    return summarize(rows)


def summarize(rows):
    nums = rows[0]["program"].keys()
    out = {"seeds": [r["seed"] for r in rows], "rows": rows,
           "program_max": {k: max(r["program"][k] for r in rows)
                           for k in nums}}
    out["program_max"]["ckpt_int8_err"] = max(r["ckpt_int8_err"]
                                              for r in rows)
    for what in ("control", "half_batch"):
        got = [r[what] for r in rows if what in r]
        if got:
            out[what + "_min"] = {k: min(g[k] for g in got) for k in nums}
    out["int4_min"] = min(r["ckpt_int8_err_int4"] for r in rows)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    from repro.launch.jax_cache import use_compile_cache
    use_compile_cache()
    cfg = harness.load_config(args.config)
    with open(os.path.join(harness.BENCH, "mixes", args.mix + ".json")) as f:
        mix = json.load(f)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = calibrate(cfg, mix, seeds, args.controls,
                    log=lambda s: print(s, file=sys.stderr))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))


if __name__ == "__main__":
    main()
