"""Each mix's whole-cycle loop and its counts, at CPU size with the
program's jnp kernel paths."""
import time

import pytest

from bench import harness
from bench_tiny import tiny_cell

# ops of one cycle, per cell
CYCLE_OPS = {"t.save": 3, "t.resume": 1, "t.train": 5, "d.train": 5}


@pytest.mark.parametrize("name", sorted(CYCLE_OPS))
def test_cell_runs_whole_cycles_and_is_correct(name):
    cell = tiny_cell(name)
    out = harness.run(cell, 2**31 + 11, 0.5, False, time.perf_counter(),
                      harness.Hooks(require_chip=False))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["attempted"] > 0
    assert out["attempted"] % CYCLE_OPS[name] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
