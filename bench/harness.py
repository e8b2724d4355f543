"""The benchmark's harness: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell's limits sits in a file of its own, found by the name that
``BENCHMARK.json`` gives:

    bench/configs/<config>.json   sizes as run, source, what was cut
    bench/mixes/<traffic>.json    the cycle of operations and its parameters
    bench/metrics/<metric>.py     a reader: ``read(ctx) -> float | None``
    bench/limits/<workload>.json  the limit of every number compared

The program is driven through its own API (``repro.launch.train``'s
``build``, ``state_struct``, ``ckpt_tree``, ``bb_config_for`` and
``restore_state``; ``BBCheckpointManager`` on a running
``BurstBufferSystem``). A mix's cycle is a list of operations:

    {"op": "step", "count": n}   n train steps, then wait for the last loss
    {"op": "save"}               a checkpoint of the current state
    {"op": "resume"}             drop the state, restore it with a new
                                 manager on the running buffer, one step

The window runs whole cycles: a new one starts only while the last one's
duration still fits in what is left of ``--seconds``, and at least one runs.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, peaks, reference, trace, weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
QUANT_BLOCK = 2048
EXACT_SAMPLES = 4096      # values sampled from each leaf saved exactly
INT8_BLOCKS = 8           # whole blocks sampled from each int8 leaf
REF_STEPS = 3             # steps the reference follows


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: str = BENCH,
              spec_path: Optional[str] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json, with its files found by name."""
    spec = _json(spec_path or os.path.join(os.path.dirname(bench_dir),
                                           "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = _json(os.path.join(os.path.dirname(bench_dir), conf["file"]))
    mix = _json(os.path.join(bench_dir, "mixes", w["traffic"] + ".json"))
    limits = _json(os.path.join(bench_dir, "limits", name + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in moved]
    return Cell(name, w["chips"], cfg, mix, limits, e2e, layer)


def load_config(name: str, bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(bench_dir, "configs", name + ".json"))


def metric_module(name: str, bench_dir: str = BENCH):
    """The reader file of a per-layer metric, ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the program


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file; raises where the
    program cannot run what the file states."""
    from repro.configs.base import get_config
    base = get_config(cfg["program_arch"])
    acts = {"gelu_pytorch_tanh": "gelu", "silu": "silu"}
    t = cfg["training"]
    if cfg["norm_epsilon"] != 1e-6:
        raise ValueError("the program's norms use epsilon 1e-6")
    return dataclasses.replace(
        base, d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        segments=((("attn",), cfg["num_hidden_layers"]),),
        rope_theta=cfg["rope_theta"], norm=cfg["norm"],
        act=acts[cfg["hidden_act"]], mlp_gated=cfg["mlp_gated"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=t["param_dtype"], compute_dtype=t["compute_dtype"],
        optimizer=t["optimizer"])


def check_program_matches(cfg: dict, struct, optimizer):
    """The program's state layout and optimizer are what the file states."""
    want = weights.param_shapes(cfg)
    got = {n: (tuple(l.shape), str(l.dtype))
           for n, l in weights.flatten(struct.params).items()}
    dt = cfg["training"]["param_dtype"]
    if got != {n: (s, dt) for n, s in want.items()}:
        raise ValueError(f"the program's parameters differ from the "
                         f"configuration: {sorted(set(got) ^ set(want))}")
    t = cfg["training"]
    fields = {"b1", "b2", "eps", "weight_decay", "decay", "clip_threshold",
              "momentum", "momentum_dtype"}
    for f in fields & set(t):
        if hasattr(optimizer, f) and getattr(optimizer, f) != t[f]:
            raise ValueError(f"optimizer {f}: program "
                             f"{getattr(optimizer, f)!r}, file {t[f]!r}")
    for s in (1, 2, 3, 200, 5000):
        s_ = jnp.asarray(s, jnp.int32)
        a, b = float(optimizer.lr(s_)), float(reference.lr_at(t, s_))
        if not math.isclose(a, b, rel_tol=1e-6):
            raise ValueError(f"learning rate at step {s}: program {a}, "
                             f"file {b}")


def leaf_items(tree) -> List[tuple]:
    """[(name, leaf)] of any pytree, names '/'-joined from its keys."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            for attr in ("key", "name", "idx"):
                if hasattr(p, attr):
                    parts.append(str(getattr(p, attr)))
                    break
        out.append(("/".join(parts), leaf))
    return out


def int8_allowed(name: str, leaf) -> bool:
    """The mix's guarantee: optimizer moments of rank 2 or more may come
    back within the blockwise int8 bound; every other leaf exactly."""
    return (name.startswith("opt_state/") and leaf.ndim >= 2
            and int(np.prod(leaf.shape)) >= QUANT_BLOCK)


def checkpoint_bytes(tree, quantize: bool) -> int:
    """Bytes of a checkpoint of ``tree`` from its shapes: exact leaves at
    their dtype's size, int8 leaves at a byte a value plus a float32 scale
    a block."""
    total = 0
    for name, leaf in leaf_items(tree):
        n = int(np.prod(leaf.shape))
        if quantize and int8_allowed(name, leaf):
            nb = -(-n // QUANT_BLOCK)
            total += nb * QUANT_BLOCK + 4 * nb
        else:
            total += n * np.dtype(leaf.dtype).itemsize
    return total


# --------------------------------------------------------------- the check


def _rng(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng((seed % 2**64, sum(map(ord, salt))))


def sample_plan(tree, seed: int) -> Dict[str, np.ndarray]:
    """Flat positions to read back from each leaf, drawn from the seed:
    single values of exact leaves, whole blocks of int8 ones."""
    plan = {}
    for name, leaf in leaf_items(tree):
        n = int(np.prod(leaf.shape))
        r = _rng(seed, name)
        if int8_allowed(name, leaf):
            nb = n // QUANT_BLOCK
            blocks = np.sort(r.choice(nb, min(nb, INT8_BLOCKS), replace=False))
            idx = (blocks[:, None] * QUANT_BLOCK
                   + np.arange(QUANT_BLOCK)[None]).reshape(-1)
        else:
            idx = np.sort(r.choice(n, min(n, EXACT_SAMPLES), replace=False))
        plan[name] = idx.astype(np.int32)
    return plan


def make_sampler(plan: Dict[str, np.ndarray]):
    idx = {n: jnp.asarray(i) for n, i in plan.items()}

    @jax.jit
    def gather(tree):
        return {n: leaf.reshape(-1)[idx[n]] for n, leaf in leaf_items(tree)}

    return lambda tree: {n: np.asarray(v) for n, v in
                         jax.device_get(gather(tree)).items()}


def compare_samples(saved: dict, restored: dict, tree_struct) -> dict:
    """``ckpt_exact_bad``: values of exactly saved leaves whose bytes differ
    (and leaves missing or of another dtype); ``ckpt_int8_err``: the largest
    |restored - saved| of an int8 leaf over its bound, half the block's
    scale (max|block| / 127) plus half a unit in the last place of the
    leaf's dtype, to which the restored value is rounded."""
    bad, worst = 0, 0.0
    kinds = {n: int8_allowed(n, l) for n, l in leaf_items(tree_struct)}
    for name, x in saved.items():
        y = restored.get(name)
        if y is None or y.shape != x.shape or y.dtype != x.dtype:
            bad += x.size
            continue
        if kinds[name]:
            worst = max(worst, int8_err(x, y))
        else:
            u = np.dtype(f"u{x.dtype.itemsize}")
            bad += int(np.count_nonzero(x.view(u) != y.view(u)))
    return {"ckpt_exact_bad": float(bad), "ckpt_int8_err": worst}


def int8_err(x: np.ndarray, y: np.ndarray) -> float:
    """Worst |y - x| over its bound, for whole blocks of a leaf saved as
    blockwise int8 (``x`` saved, ``y`` restored, of the leaf's dtype)."""
    nmant = int(jnp.finfo(x.dtype).nmant)
    xb = x.astype(np.float32).reshape(-1, QUANT_BLOCK)
    yb = y.astype(np.float32).reshape(-1, QUANT_BLOCK)
    half_step = np.maximum(np.abs(xb).max(1, keepdims=True) / 127,
                           1e-12) / 2
    mag = np.maximum(np.abs(yb), np.float32(1e-30))
    half_ulp = np.exp2(np.floor(np.log2(mag)) - nmant - 1)
    return float((np.abs(yb - xb) / (half_step + half_ulp)).max())


def gap(prog: float, ref: float, floor: float) -> float:
    return abs(prog - ref) / max(abs(ref), floor)


def training_numbers(prog: dict, ref: dict) -> dict:
    """``loss_gap``: the largest relative gap of a step's loss; ``grad_gap``
    and ``update_gap``: the worst leaf's gap between the program's norm and
    the reference's (first clipped gradient; change over the steps), over
    the larger of that leaf's reference norm and the median leaf's. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone and are left out of ``update_gap``."""
    losses = list(zip(prog["losses"], ref["losses"]))
    losses += [(l, ref["extra_loss"]) for l in prog.get("resumed", [])]
    loss_gap = max(gap(a, b, 0.0) for a, b in losses)
    rg = ref["grad_norms"]
    med_g = statistics.median(rg.values())
    grad_gap = max(gap(prog["grad_norms"][n], rg[n], med_g) for n in rg)
    moved = [n for n in rg if rg[n] >= 1e-3 * med_g]
    rc = ref["change_norms"]
    med_c = statistics.median(rc[n] for n in moved)
    update_gap = max(gap(prog["change_norms"][n], rc[n], med_c)
                     for n in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


# ------------------------------------------------------------------ a run


@dataclasses.dataclass
class Hooks:
    """Seams for the benchmark's own tests: what runs where the chip is
    looked for, and what wraps the program's step and its save."""
    require_chip: bool = True
    wrap_step: Optional[Callable] = None
    wrap_save: Optional[Callable] = None


class Feed:
    """The seed's batches in order, put on the device one at a time."""

    def __init__(self, seed, cfg, mix):
        self.seed, self.index = seed, 0
        self.shape = dict(batch=mix["batch"], seq_len=mix["seq_len"],
                          vocab=cfg["vocab_size"])
        self.tokens = mix["batch"] * mix["seq_len"]

    def host(self, index):
        return data.batch_at(self.seed, index, **self.shape)

    def at(self, index):
        return {k: jnp.asarray(v) for k, v in self.host(index).items()}

    def next(self):
        b = self.at(self.index)
        self.index += 1
        return b


def _opt_grad_norms(optimizer_kind: str, b1: float):
    """The first gradient as the optimizer got it, per leaf, read from its
    state after one step: AdamW's m is (1 - b1) g; Adafactor's factored
    row moment is mean(g^2) over the last axis (its beta2 is 0 at step 1)."""
    def norms(opt_state, params):
        out = {}
        pf = weights.flatten(params)
        if optimizer_kind == "adamw":
            for n, m in weights.flatten(opt_state.m).items():
                out[n] = jnp.sqrt(jnp.sum(jnp.square(
                    m.astype(jnp.float32)))) / (1 - b1)
        else:
            for n, vr in weights.flatten(opt_state.vr).items():
                s = jnp.sum(vr.astype(jnp.float32))
                if pf[n].ndim >= 2:
                    s = s * pf[n].shape[-1]
                out[n] = jnp.sqrt(s)
        return out
    return jax.jit(norms)


class Program:
    """The program under test for one configuration, built once: the model,
    its optimizer, its jitted donating step, and the benchmark's jitted
    reads of its state."""

    def __init__(self, cfg: dict, hooks: "Hooks"):
        from repro.launch import train
        self.cfg = cfg
        self.model, self.optimizer, step = train.build(program_config(cfg))
        self.struct = train.state_struct(self.model, self.optimizer)
        check_program_matches(cfg, self.struct, self.optimizer)
        self.step = hooks.wrap_step(step) if hooks.wrap_step else step
        t = cfg["training"]
        self._grad_norms = _opt_grad_norms(t["optimizer"], t.get("b1", 0.0))
        self._init = jax.jit(lambda key: weights.make_params(cfg, key))
        self._opt_init = jax.jit(self.optimizer.init)

        # the start is made apart and handed in: made inside the same
        # program, XLA may skip its rounding to the parameter dtype
        @jax.jit
        def change(params, start):
            start = weights.flatten(start)
            return {n: jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32) - start[n].astype(jnp.float32))))
                for n, p in weights.flatten(params).items()}
        self._change = change

    def first_steps(self, seed: int, feed: "Feed"):
        """A new state from the seed's weights, driven through its first
        steps by the window's own call and feed. Returns the state and what
        the reference is compared with: each step's loss, the first
        gradient's norm per leaf, each leaf's change over the steps."""
        from repro.runtime.train_step import TrainState
        key = weights.seed_key(seed, "weights")
        params = self._init(key)
        state = TrainState(params, self._opt_init(params))
        del params
        prog = {"losses": [], "resumed": []}
        for i in range(REF_STEPS):
            state, m = self.step(state, feed.next())
            prog["losses"].append(float(m["loss"]))
            if i == 0:
                prog["grad_norms"] = _floats(self._grad_norms(
                    state.opt_state, state.params))
        prog["change_norms"] = _floats(self._change(state.params,
                                                    self._init(key)))
        return state, prog


def _floats(tree) -> dict:
    return {n: float(v) for n, v in jax.device_get(tree).items()}


def warm_save_path(struct, quantize: bool):
    """Compile every program the save and restore paths run at this state's
    leaf sizes: the flatten, pad and quantize of each int8 leaf, and the
    dequantize that restores it."""
    from repro.kernels import ops as kops
    if not quantize:
        return
    seen = set()
    for name, leaf in leaf_items(struct):
        if not int8_allowed(name, leaf) or (leaf.shape, leaf.dtype) in seen:
            continue
        seen.add((leaf.shape, leaf.dtype))
        flat = jnp.zeros(leaf.shape, leaf.dtype).reshape(-1).astype(
            jnp.float32)
        pad = (-flat.shape[0]) % QUANT_BLOCK
        if pad:
            flat = jnp.pad(flat, (0, pad))
        q, s = kops.quantize_blockwise(flat, block=QUANT_BLOCK)
        x = kops.dequantize_blockwise(q, s, block=QUANT_BLOCK)
        jax.block_until_ready(x)


def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        hooks: Hooks = Hooks(), log=print) -> dict:
    devices = jax.devices()
    if hooks.require_chip and (devices[0].platform != "tpu"
                               or len(devices) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX finds "
                     f"{len(devices)} {devices[0].platform} device(s)")
    dev = devices[0]
    peak = peaks.peaks_for(dev.device_kind) if hooks.require_chip else None

    from repro.checkpoint import serializer as ser
    from repro.checkpoint.bbckpt import BBCheckpointManager
    from repro.core import BurstBufferSystem, telemetry
    from repro.launch import train
    cfg, mix = cell.config, cell.mix
    ops = [o["op"] for o in mix["cycle"]]
    quantize = bool(mix.get("quantize", False))
    needs_bb = "save" in ops or "resume" in ops or mix.get("setup_save")

    program = Program(cfg, hooks)
    model, optimizer, step_fn = program.model, program.optimizer, program.step
    struct = program.struct
    feed = Feed(seed, cfg, mix)

    # ---- set-up: weights from the seed, the first steps through the
    # window's own call and feed, read for the reference
    state, prog = program.first_steps(seed, feed)

    if traced:
        telemetry.enable()
    ckpt_struct = train.ckpt_tree(struct, 0)
    policy = ser.default_quant_policy if quantize else None
    bb = mgr = None
    if needs_bb:
        bb = BurstBufferSystem(train.bb_config_for(
            ser.tree_nbytes(ckpt_struct, policy))).start()
        mgr = BBCheckpointManager(bb, quantize=quantize,
                                  io_mode=mix["io_mode"])
        warm_save_path(ckpt_struct, quantize)
    sampler = make_sampler(sample_plan(ckpt_struct, seed))
    saved_sample = None
    steps_done = REF_STEPS
    if mix.get("setup_save"):
        mgr.save(steps_done, train.ckpt_tree(state, feed.index))
        saved_sample = sampler(train.ckpt_tree(state, feed.index))
        mgr.wait_flushes()
    elif needs_bb:
        sampler(train.ckpt_tree(state, feed.index))   # compile it

    # ---- the window
    rec = {"steps": 0, "stretches": [], "saves": [], "resumes": [],
           "step_losses": []}
    watchers: List[threading.Thread] = []
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tmp, profiler_options=opts)
        t_win0 = time.perf_counter()
        setup_s = t_win0 - t_start
        log(f"bench: set-up {setup_s:.2f} s; window of {seconds} s")
        cycles = 0
        with CompileCount() as compiles, \
                jax.profiler.TraceAnnotation("bench.window"):
            while True:
                c0 = time.perf_counter()
                for op in mix["cycle"]:
                    if op["op"] == "step":
                        s0 = time.perf_counter()
                        with jax.profiler.TraceAnnotation("bench.step"):
                            for _ in range(op["count"]):
                                state, m = step_fn(state, feed.next())
                                rec["step_losses"].append(m["loss"])
                            jax.block_until_ready(m["loss"])
                        rec["steps"] += op["count"]
                        steps_done += op["count"]
                        rec["stretches"].append(
                            (op["count"], time.perf_counter() - s0))
                    elif op["op"] == "save":
                        with jax.profiler.TraceAnnotation("bench.wait"):
                            jax.block_until_ready(state)
                        tree = train.ckpt_tree(state, feed.index)
                        t_call = time.perf_counter()
                        with jax.profiler.TraceAnnotation("bench.save"):
                            save = hooks.wrap_save(mgr.save) \
                                if hooks.wrap_save else mgr.save
                            save(steps_done, tree)
                        t_ret = time.perf_counter()
                        entry = {"step": steps_done, "stall": t_ret - t_call,
                                 "t_call": t_call}
                        rec["saves"].append(entry)
                        watchers.append(_watch_flush(mgr, entry))
                        saved_sample = sampler(tree)
                        del tree
                    elif op["op"] == "resume":
                        t0 = time.perf_counter()
                        with jax.profiler.TraceAnnotation("bench.restore"):
                            state = None
                            fresh = BBCheckpointManager(
                                bb, quantize=quantize, io_mode=mix["io_mode"])
                            state, data_step, _ = train.restore_state(
                                fresh, model, optimizer)
                        with jax.profiler.TraceAnnotation("bench.step"):
                            state, m = step_fn(state, feed.at(data_step))
                            loss = float(m["loss"])
                        rec["resumes"].append(time.perf_counter() - t0)
                        prog["resumed"].append(loss)
                    else:
                        raise ValueError(f"unknown op {op['op']!r}")
                cycles += 1
                c1 = time.perf_counter()
                if (c1 - t_win0) + (c1 - c0) > seconds:
                    break
        t_win1 = time.perf_counter()
        window_s = t_win1 - t_win0
        log(f"bench: window {window_s:.2f} s, {cycles} cycle(s); "
            f"{compiles.compiled} program(s) compiled and "
            f"{compiles.requests} asked of the compiler inside it")
        tr = None
        if traced:
            jax.profiler.stop_trace()
            tr = trace.load(trace.find_xplane(tmp))

        # ---- after the window: every flush, the device's peak, the check
        failed = 0
        if mgr is not None:
            try:
                mgr.wait_flushes()
            except TimeoutError:
                pass
            for w in watchers:
                w.join()
            failed += sum(1 for s in rec["saves"] if "durable" not in s)
        rec["step_losses"] = [float(x) for x in rec["step_losses"]]
        failed += sum(1 for x in rec["step_losses"] + prog["resumed"]
                      if not math.isfinite(x))
        attempted = rec["steps"] + len(rec["saves"]) + len(rec["resumes"])
        memory_peak = int((dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
        ctx = {"cell": cell, "rec": rec, "window_s": window_s,
               "tokens_per_step": feed.tokens, "peaks": peak,
               "chips": cell.chips, "config": cfg, "mix": mix,
               "struct": ckpt_struct, "quantize": quantize, "trace": tr,
               "mgr_metrics": dict(mgr.metrics) if mgr else {}, "spans": []}
        if traced:
            ctx["spans"] = [{"name": e[3], "dur": e[6], "args": e[7]}
                            for e in telemetry.registry().tracer.events()]
            telemetry.disable()

        numbers = {}
        state = None
        gc.collect()
        if saved_sample is not None:
            # the newest checkpoint, read back from the PFS copy
            if rec["saves"]:
                bb.evict(f"ckpt_{rec['saves'][-1]['step']:08d}")
            fresh = BBCheckpointManager(bb, quantize=quantize,
                                        io_mode=mix["io_mode"])
            restored, data_step, _ = train.restore_state(fresh, model,
                                                         optimizer)
            got = sampler(train.ckpt_tree(restored, data_step))
            del restored
            numbers.update(compare_samples(saved_sample, got, ckpt_struct))
    finally:
        if bb is not None:
            bb.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    gc.collect()
    log(f"bench: read-back done {time.perf_counter() - t_win1:.2f} s "
        f"after the window")
    batches = [feed.host(i) for i in range(REF_STEPS)]
    extra = feed.at(REF_STEPS) if prog["resumed"] else None
    ref = reference.train(cfg, seed, batches, steps=REF_STEPS,
                          extra_loss_batch=extra, extra_loss_after=REF_STEPS)
    numbers.update(training_numbers(prog, ref))
    log(f"bench: reference done {time.perf_counter() - t_win1:.2f} s after "
        f"the window")
    # a number is compared where the cell's limits give it a limit
    for k in sorted(set(numbers) - set(cell.limits)):
        log(f"bench: {k} {numbers[k]!r} (not compared)")
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]}
              for k in sorted(numbers) if k in cell.limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if traced:
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        layer = {}
        for m in cell.per_layer:
            v = metric_module(m["name"]).read(ctx)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = layer
        out["breakdown"] = {"device_ops": trace.top_ops(tr),
                            "idle_gaps": trace.idle_gaps(tr)}
    else:
        out["metrics"] = end_to_end_metrics(cell, rec, window_s, setup_s,
                                            feed.tokens)
    out["device"] = device
    out["checks"] = checks
    return out


class CompileCount:
    """Programs compiled, and programs asked of the compiler at all (loaded
    from the persistent cache or compiled), while the block runs."""

    def __init__(self):
        self.compiled = self.requests = 0

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False


def _watch_flush(mgr, entry) -> threading.Thread:
    """Stamp the save's durability when its flush thread ends acknowledged.
    The program gives no public signal of a flush's end yet, so this reads
    its manager's flush threads and unacknowledged steps; it fails loudly
    where those no longer hold this save's flush."""
    step = entry["step"]
    flusher = mgr._flush_threads[-1] if mgr._flush_threads else None
    if flusher is None or flusher.name != f"ckpt-flush-{step}":
        raise RuntimeError(f"no flush thread of step {step} to watch: the "
                           f"program's flush bookkeeping has changed")

    def wait():
        flusher.join()
        t = time.perf_counter()
        if step not in mgr._unflushed:
            entry["durable"] = t - entry["t_call"]

    w = threading.Thread(target=wait, daemon=True, name=f"bench-ack-{step}")
    w.start()
    return w


def end_to_end_metrics(cell: Cell, rec: dict, window_s: float,
                       setup_s: float, tokens_per_step: int) -> dict:
    vals = {"setup_s": setup_s}
    if rec["steps"]:
        vals["train_tokens_per_s"] = rec["steps"] * tokens_per_step / window_s
    if rec["saves"]:
        vals["save_stall_s"] = sum(s["stall"] for s in rec["saves"]) \
            / len(rec["saves"])
        acked = [s["durable"] for s in rec["saves"] if "durable" in s]
        if acked:
            vals["durable_s"] = sum(acked) / len(acked)
    if rec["resumes"]:
        vals["resume_s"] = sum(rec["resumes"]) / len(rec["resumes"])
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in vals:
            raise RuntimeError(f"{cell.name}: no reading of {m['name']}")
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out
