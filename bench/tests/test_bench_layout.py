"""A configuration, a traffic mix, a per-layer metric and a cell's limits
are found by name from the files alone: adding them edits no file that is
already there."""
import json
import os
import shutil

from bench import harness
from bench_tiny import TINY


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(TINY, root)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "tiny-sc2.json").read_text())
    cfg["name"] = "tiny-new"
    (bench / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "mixes" / "train.json").read_text())
    mix["cycle"] = [{"op": "step", "count": 2}]
    (bench / "mixes" / "burst.json").write_text(json.dumps(mix))
    (bench / "limits" / "n.burst.json").write_text(
        json.dumps({"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}))
    (bench / "metrics").mkdir()
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['rec']['steps'])\n")

    spec_path = root / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["configs"].append({"name": "tiny-new", "source": "test",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "n.burst", "config": "tiny-new",
                              "traffic": "burst", "chips": 1, "why": "t"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("n.burst")
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["n.burst"]})
    spec_path.write_text(json.dumps(spec))

    cell = harness.load_cell("n.burst", bench_dir=str(bench))
    assert cell.config["name"] == "tiny-new"
    assert cell.mix["cycle"] == [{"op": "step", "count": 2}]
    assert cell.limits["loss_gap"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                   "setup_s"}
    read = harness.metric_module("steps_seen", str(bench)).read
    assert read({"rec": {"steps": 4}}) == 4.0
    for p, data in before.items():
        if p != spec_path:
            assert p.read_bytes() == data, p


def test_the_benchmarks_own_cells_have_their_files():
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_module(m["name"]).read)
