"""Compile a configuration's train step for a TPU v5e that is described, not
attached, and print what the compiler reports: the bytes of arguments,
outputs, aliases and temporaries on the chip, and whether the Pallas kernels
are in the program. Nothing runs; no time is measured.

    JAX_PLATFORMS=cpu python bench/rehearse_compile.py \\
        --config bench/configs/deepseek-coder-33b.2l.json --batch 1 --seq 4096
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--reference", action="store_true",
                    help="compile the plain reference's step instead")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.kernels import ops
    from repro.launch import train

    jax.config.update("jax_enable_compilation_cache", False)
    # the kernels pick their TPU path from the default backend, which here
    # is the CPU: steer them to the path the chip runs
    ops._on_tpu = lambda: True
    with open(args.config) as f:
        cfg = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    model, optimizer, step_fn = train.build(harness.program_config(cfg))
    struct = train.state_struct(model, optimizer)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        struct)
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32,
                                     sharding=chip)
             for k in ("inputs", "labels")}
    t0 = time.perf_counter()
    if args.reference:
        from bench import reference, weights
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            jax.eval_shape(lambda: weights.make_params(
                cfg, jax.random.PRNGKey(0))))
        with jax.default_matmul_precision("highest"):
            compiled = reference._grads.lower(
                reference._key(cfg), params, batch, None, False).compile()
    else:
        compiled = step_fn.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    report = {
        "config": cfg["name"], "batch": args.batch, "seq": args.seq,
        "compile_s_on_this_host": time.perf_counter() - t0,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        **{k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")},
    }
    print(json.dumps(report))


if __name__ == "__main__":
    main()
