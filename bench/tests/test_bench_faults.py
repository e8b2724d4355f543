"""The check fails a run whose timed path is broken underneath, and the
control (the plain reference in a precision below the configuration's, in
the program's place) fails the limits. CPU size, the chip's look skipped."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, harness, reference
from bench_tiny import tiny_cell


def _run(name, **hooks):
    return harness.run(tiny_cell(name), 12345, 0.3, False,
                       time.perf_counter(),
                       harness.Hooks(require_chip=False, **hooks))


def _unchanged(step):
    def faulty(state, batch):
        _, m = step(jax.tree.map(jnp.copy, state), batch)
        return state, m
    return faulty


def _half_batch(step):
    def faulty(state, batch):
        half = batch["inputs"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    return faulty


def _altered(save):
    def faulty(step, tree):
        p = tree["params"]
        scale = p["final_norm"]["scale"]
        p = {**p, "final_norm": {**p["final_norm"], "scale": scale + 1}}
        return save(step, {**tree, "params": p})
    return faulty


def _int4_moments(save):
    """The moments saved at int4's precision where int8 is promised."""
    def faulty(step, tree):
        leaves = [calibrate.int4_round(leaf)
                  if harness.int8_allowed(name, leaf) else leaf
                  for name, leaf in harness.leaf_items(tree)]
        return save(step, jax.tree.unflatten(jax.tree.structure(tree),
                                             leaves))
    return faulty


@pytest.mark.parametrize("name,hooks", [
    ("t.train", {"wrap_step": _unchanged}),
    ("t.train", {"wrap_step": _half_batch}),
    ("t.save", {"wrap_save": _altered}),
    ("t.save", {"wrap_save": _int4_moments}),
], ids=["state-unchanged", "half-batch", "answer-altered", "int4-moments"])
def test_fault_makes_run_incorrect(name, hooks):
    out = _run(name, **hooks)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["t.train", "d.train"])
def test_control_fails_the_limits(cell):
    c = tiny_cell(cell)
    cfg, seed = c.config, 7
    feed = harness.Feed(seed, cfg, c.mix)
    batches = [feed.host(i) for i in range(harness.REF_STEPS)]
    ref = reference.train(cfg, seed, batches, steps=harness.REF_STEPS)
    low = calibrate.LOWER[cfg["training"]["compute_dtype"]]
    ctl = reference.train(cfg, seed, batches, steps=harness.REF_STEPS,
                          low=low)
    ctl["resumed"] = []
    got = harness.training_numbers(ctl, ref)
    assert any(v > c.limits[k] for k, v in got.items()), got
