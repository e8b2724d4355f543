"""The program's spans as the benchmark reads them: the per-layer metrics
that split a save and a restore into their parts, and the idle gaps named
after the spans of the benchmark's own thread in a profiler trace."""
import dataclasses
import os
import threading
import time

import numpy as np
import pytest

from bench import harness, spans, trace
from bench_tiny import tiny_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "trace_small.xplane.pb")

SAVE_METRICS = ("fetch_s", "quantize_s", "compile_s.save",
                "host_rss_peak.save")
RESUME_METRICS = ("restore_read_s", "dequantize_s", "place_s",
                  "compile_s.resume", "host_rss_peak.resume")


def _s(name, dur, **args):
    return {"name": name, "dur": dur, "args": args}


def _save_spans(step, scale):
    """One save's spans as the program records them."""
    return [
        _s("ckpt.fetch", 1.0 * scale, step=step, leaf="a", bytes=8),
        _s("ckpt.fetch", 2.0 * scale, step=step, leaf="b", bytes=8),
        _s("jax.compile", 0.5 * scale, step=step, **{"in": "ckpt.quantize"}),
        _s("ckpt.quantize", 3.0 * scale, step=step, leaf="b", bytes=4),
        _s("ckpt.serialize", 6.0 * scale, step=step,
           rss_peak_bytes=int(4e9 * scale)),
        _s("ckpt.pwrite", 1.0, step=step, bytes=12),
        _s("ckpt.barrier", 1.0, step=step),
        _s("ckpt.save", 2.0, step=step, rss_peak_bytes=int(5e9 * scale)),
        _s("ckpt.flush", 9.0, step=step, bytes=12, acked=True),
    ]


def _restore_spans(scale):
    return [
        _s("ckpt.stage", 0.5 * scale, step=3),
        _s("ckpt.read", 1.5 * scale, step=3),
        _s("jax.compile", 0.25 * scale, step=3, **{"in": "ckpt.dequantize"}),
        _s("ckpt.dequantize", 2.0 * scale, step=3, leaf="b", bytes=8),
        _s("ckpt.place", 0.5 * scale, step=3, leaf="a", bytes=8),
        _s("ckpt.place", 0.5 * scale, step=3, leaf="b", bytes=8),
        _s("ckpt.restore", 5.0 * scale, step=3,
           rss_peak_bytes=int(6e9 * scale)),
    ]


def _save_ctx():
    # set-up's save at step 3 is not one of the window's saves
    return {"rec": {"saves": [{"step": 10}, {"step": 20}], "resumes": []},
            "spans": _save_spans(3, 100.0) + _save_spans(10, 1.0)
            + _save_spans(20, 2.0)}


def _resume_ctx():
    return {"rec": {"saves": [], "resumes": [7.0, 9.0]},
            "spans": _save_spans(3, 1.0) + _restore_spans(1.0)
            + _restore_spans(3.0)}


def _parent(ctx):
    """What a program without the new spans records: only the roots that
    it had, without their host peaks."""
    keep = ("ckpt.save", "ckpt.flush", "ckpt.restore")
    return {**ctx, "spans": [_s(s["name"], s["dur"], step=s["args"]["step"])
                             for s in ctx["spans"] if s["name"] in keep]}


@pytest.mark.parametrize("name,want", [
    ("fetch_s", (3.0 + 6.0) / 2),
    ("quantize_s", (3.0 + 6.0) / 2),
    ("compile_s.save", (0.5 + 1.0) / 2),
    ("host_rss_peak.save", (5.0 + 10.0) / 2),
    ("restore_read_s", (2.0 + 6.0) / 2),
    ("dequantize_s", (2.0 + 6.0) / 2),
    ("place_s", (1.0 + 3.0) / 2),
    ("compile_s.resume", (0.25 + 0.75) / 2),
    ("host_rss_peak.resume", (6.0 + 18.0) / 2),
])
def test_metric_reads_the_programs_spans(name, want):
    ctx = _save_ctx() if name in SAVE_METRICS else _resume_ctx()
    read = harness.metric_module(name).read
    assert read(ctx) == pytest.approx(want)
    # silent where the program records none of the new spans
    assert read(_parent(ctx)) is None
    assert read({**ctx, "spans": []}) is None


def test_compiles_outside_the_save_are_not_counted():
    ctx = _save_ctx()
    ctx["spans"].append(_s("jax.compile", 40.0, step=10,
                           **{"in": "ckpt.flush"}))
    read = harness.metric_module("compile_s.save").read
    assert read(ctx) == pytest.approx((0.5 + 1.0) / 2)


def test_the_new_metrics_are_declared_for_their_cells():
    for cell, names in (("dsl7b-2l.save-int8", SAVE_METRICS),
                        ("dsl7b-2l.resume", RESUME_METRICS)):
        layer = {m["name"]: m for m in harness.load_cell(cell).per_layer}
        for name in names:
            assert layer[name]["source"] == "program_span"
            assert layer[name]["workloads"] == [cell]


# ------------------------------------------------------------ on the CPU


@pytest.fixture
def telemetry_off():
    """Telemetry off for the test, as in a run of its own, and back on
    after it where a test before it left it on."""
    from repro.core import telemetry
    was_on = telemetry.enabled()
    telemetry.disable()
    yield
    if was_on:
        telemetry.enable()


@pytest.mark.usefixtures("telemetry_off")
@pytest.mark.parametrize("name,metrics,whole", [
    ("t.save", SAVE_METRICS, "serialize_s"),
    ("t.resume", RESUME_METRICS, "restore_s"),
])
def test_a_traced_tiny_run_reports_the_split(name, metrics, whole):
    """The program's own spans, through the harness's traced run: every new
    metric reads, and the parts sum to no more than the whole they split."""
    cell = tiny_cell(name)
    spec = {m["name"]: m for m in harness.load_cell(
        "dsl7b-2l.save-int8" if name == "t.save"
        else "dsl7b-2l.resume").per_layer}
    layer = [spec[m] for m in metrics + (whole,)]
    cell = dataclasses.replace(cell, per_layer=layer)
    out = harness.run(cell, 2**31 + 5, 0.3, True, time.perf_counter(),
                      harness.Hooks(require_chip=False))
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == set(metrics) | {whole}, got
    assert all(v > 0 for k, v in got.items() if not k.startswith("compile"))
    parts = [v for k, v in got.items()
             if k.endswith("_s") and k != whole]
    assert 0 < sum(parts) <= got[whole] * 1.01 + 1e-3


# ---------------------------------------------------------- in the trace


def test_recorded_chip_trace_reads_as_before():
    t = trace.load(SMALL)
    found = spans.load(SMALL)
    assert found.own == [] and found.other == []
    assert spans.idle_gaps(t, found.own) == trace.idle_gaps(t)
    assert t.window_s == pytest.approx(0.053317697)
    assert spans.idle_inside(t, "bench.wait", []) == 0.0


def test_gaps_are_named_after_the_innermost_span_of_the_marks_thread():
    ops = {0: [(0, 100, "a"), (400, 1000, "b")]}
    marks = [(0, 1000, "bench.window"), (50, 900, "bench.save")]
    t = trace.from_events(ops, marks)
    own = [(80, 450, "ckpt.serialize"), (120, 380, "ckpt.quantize")]
    assert trace.idle_gaps(t) == [["bench.save", pytest.approx(300e-9)]]
    assert spans.idle_gaps(t, own) == [["ckpt.quantize",
                                        pytest.approx(300e-9)]]
    assert t.window == (0, 1000)


def test_idle_inside_a_mark_while_another_threads_span_is_open():
    ops = {0: [(0, 100, "a"), (300, 400, "b"), (700, 1000, "c")]}
    marks = [(0, 1000, "bench.window"), (100, 500, "bench.step"),
             (600, 800, "bench.step")]
    t = trace.from_events(ops, marks)
    flush = [(200, 650, "ckpt.flush"), (2000, 3000, "ckpt.flush")]
    # inside the steps and the flush: [200, 500) less [300, 400) busy,
    # and [600, 650) idle
    assert spans.idle_inside(t, "bench.step", flush) == pytest.approx(
        (200 + 50) * 1e-9)


@pytest.mark.usefixtures("telemetry_off")
def test_a_save_under_the_profiler_puts_its_spans_on_the_marks_thread(
        tmp_path):
    """A small save with telemetry on, traced by the profiler on the CPU:
    its spans are on the thread of the benchmark's marks, a span another
    thread holds open names no gap and leaves the window alone."""
    import jax
    from repro.checkpoint.bbckpt import BBCheckpointManager
    from repro.core import BBConfig, BurstBufferSystem, telemetry
    telemetry.enable()
    sys_ = BurstBufferSystem(BBConfig(num_servers=2, num_clients=1,
                                      dram_capacity=8 << 20)).start()
    other_open = threading.Event()

    def other_thread():
        with jax.profiler.TraceAnnotation("ckpt.flush", step=0):
            other_open.set()
            time.sleep(0.3)

    try:
        mgr = BBCheckpointManager(sys_, quantize=True)
        state = {"opt_state": {"m": np.ones((64, 64), np.float32)},
                 "params": {"w": np.ones((64, 64), np.float32)}}
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                with jax.profiler.TraceAnnotation("bench.save"):
                    mgr.save(5, state)
                th = threading.Thread(target=other_thread)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    th.start()
                    other_open.wait()
                    time.sleep(0.1)
            th.join()
            mgr.wait_flushes()
        finally:
            jax.profiler.stop_trace()
    finally:
        sys_.stop()
        telemetry.disable()
    path = trace.find_xplane(str(tmp_path))
    t = trace.load(path)
    found = spans.load(path)
    own = {n for _, _, n in found.own}
    assert {"ckpt.serialize", "ckpt.fetch", "ckpt.quantize", "ckpt.save",
            "ckpt.pwrite", "ckpt.barrier"} <= own
    assert sum(1 for *_, n in found.own if n == "ckpt.fetch") == 2
    assert "ckpt.flush" not in own
    assert sum(1 for *_, n in found.other if n == "ckpt.flush") >= 2
    (w0, w1, _), = [m for m in t.marks if m[2] == "bench.window"]
    assert t.window == (w0, w1)
    other = max((m for m in found.other if m[2] == "ckpt.flush"),
                key=lambda m: m[1])
    assert other[1] > w1          # held open past the window
    # a device idle from the save's end to the window's end: the gap is
    # named after the marks' thread, never the other thread's span
    (save,) = [m for m in t.marks if m[2] == "bench.save"]
    t = trace.from_events({0: [(w0, save[1], "op")]}, t.marks,
                          window=t.window)
    (gap,) = spans.idle_gaps(t, found.own)
    assert gap[0] == "bench.wait"
