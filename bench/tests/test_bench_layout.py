"""A configuration, a traffic mix, a per-layer metric and a cell's limits
are found by name from the files alone: adding them edits no file that is
already there; a configuration brings its own reference module, whose
layout the program has to hold, and ``program`` fields."""
import json
import os
import shutil
import time

import pytest

from bench import harness
from bench_tiny import TINY, tiny_cell


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


def _own_layout(tmp_path, reference_text):
    """A checkout with one more configuration, ``tiny-own``: tiny-sc2's sizes
    with a reference module of its own, ``bench/own_reference.py``, and a
    ``program`` block, and its cell ``o.train``. Returns the cell, the
    reference's path and every file that was there before, with its bytes."""
    root = tmp_path / "checkout"
    shutil.copytree(TINY, root)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    ref_path = bench / "own_reference.py"
    ref_path.write_text(reference_text)
    cfg = json.loads((bench / "configs" / "tiny-sc2.json").read_text())
    cfg.update(name="tiny-own", reference="bench/own_reference.py",
               program={"segments": [[["attn"], cfg["num_hidden_layers"]]],
                        "remat": "full"})
    (bench / "configs" / "tiny-own.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "limits" / "t.train.json",
                bench / "limits" / "o.train.json")
    spec_path = root / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["configs"].append({"name": "tiny-own", "source": "test",
                            "file": "bench/configs/tiny-own.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "o.train", "config": "tiny-own",
                              "traffic": "train", "chips": 1, "why": "t"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("o.train")
    spec_path.write_text(json.dumps(spec))
    before.pop(spec_path)
    return harness.load_cell("o.train", bench_dir=str(bench)), ref_path, \
        before


def _reference_text():
    with open(os.path.join(harness.BENCH, "reference.py")) as f:
        return f.read()


def _run(cell):
    return harness.run(cell, 2**31 + 29, 0.3, False, time.perf_counter(),
                       harness.Hooks(require_chip=False))


def test_a_layout_of_its_own_is_taken_with_new_files_only(tmp_path):
    cell, ref_path, before = _own_layout(tmp_path, _reference_text())
    reference = harness.reference_for(cell.config)
    assert os.path.samefile(reference.__file__, ref_path)
    assert harness.program_config(cell.config) == harness.program_config(
        tiny_cell("t.train").config)
    got = _run(cell)
    want = _run(tiny_cell("t.train"))
    assert got["correct"], got["checks"]
    assert got["checks"] == want["checks"]
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_a_layout_the_program_does_not_hold_is_refused(tmp_path):
    text = _reference_text()
    renamed = text.replace('out[f"{layer}/mlp/w_up"]',
                           'out[f"{layer}/mlp/w_in"]')
    assert renamed != text
    cell, _, _ = _own_layout(tmp_path, renamed)
    with pytest.raises(ValueError, match="w_in"):
        harness.Program(cell.config, harness.Hooks(require_chip=False))


def test_a_program_field_the_program_lacks_is_refused_by_name():
    cfg = dict(tiny_cell("t.train").config,
               program={"remat": "full", "no_such_field": 1})
    with pytest.raises(ValueError, match="no_such_field"):
        harness.program_config(cfg)
