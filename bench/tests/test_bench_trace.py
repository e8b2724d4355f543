"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "trace_small.xplane.pb")


def _synthetic():
    ops = {0: [(100, 200, "fusion.1"), (150, 300, "_quant_kernel"),
               (500, 600, "fusion.1"), (900, 1000, "_attn_kernel")],
           1: [(100, 400, "fusion.2")]}
    marks = [(0, 1000, "bench.window"), (300, 500, "bench.save"),
             (600, 900, "bench.wait")]
    return trace.from_events(ops, marks)


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4),
                                                              (5, 10)]


def test_busy_and_idle_share():
    t = _synthetic()
    assert t.window == (0, 1000)
    # chip 0: [100, 300) + [500, 600) + [900, 1000) = 400 ns; chip 1: 300
    assert trace.busy_s(t) == pytest.approx(350e-9)
    assert trace.idle_share(t) == pytest.approx(65.0)


def test_kernel_time_sums_events():
    t = _synthetic()
    assert trace.kernel_time(t, r"_quant_kernel") == (pytest.approx(150e-9),
                                                      1)
    assert trace.kernel_time(t, r"fusion") == (pytest.approx(500e-9), 3)


def test_idle_gaps_are_named_by_the_covering_mark():
    gaps = trace.idle_gaps(_synthetic())
    assert gaps[0] == ["bench.wait", pytest.approx(300e-9)]
    assert gaps[1] == ["bench.save", pytest.approx(200e-9)]
    assert gaps[2] == ["bench.window", pytest.approx(100e-9)]


def test_top_ops_orders_by_device_time():
    top = trace.top_ops(_synthetic(), k=2)
    assert [name for name, _ in top] == ["fusion.2", "fusion.1"]


def test_a_trace_recorded_on_the_chip():
    """Three small matmuls, a 50 ms sleep and one quantize call, traced on
    a v5e; the values below were read off the trace by hand."""
    from bench.harness import metric_module
    t = trace.load(SMALL)
    assert list(t.ops) == [0]
    assert t.window_s == pytest.approx(0.053317697)
    assert 0 < trace.busy_s(t) < 0.001
    assert trace.idle_share(t) == pytest.approx(
        100 * (1 - trace.busy_s(t) / t.window_s))
    pattern = metric_module("quantize_roofline").KERNEL
    assert trace.kernel_time(t, pattern) == (pytest.approx(266e-9), 1)
    assert trace.kernel_time(
        t, metric_module("flash_fwd_roofline").KERNEL) == (0.0, 0)
    gaps = trace.idle_gaps(t)
    assert gaps[0][0] == "bench.wait"
    assert gaps[0][1] == pytest.approx(0.0509, rel=0.05)


def test_save_mfu_reads_the_traced_save_spans():
    """A save's bytes at HBM bandwidth over the traced ``bench.save`` time;
    nothing without a save or a trace."""
    import jax
    import numpy as np
    from bench.harness import metric_module
    read = metric_module("save_mfu").read
    struct = {"params": {"w": jax.ShapeDtypeStruct((64, 64), np.float32)}}
    ctx = {"trace": _synthetic(), "peaks": {"hbm_bytes_per_s": 1e9},
           "rec": {"saves": [{"step": 3}]}, "struct": struct,
           "quantize": False}
    # 64 x 64 f32 read and written: 32,768 B, 32.768 us at 1 GB/s, over
    # the 200 ns save span
    assert read(ctx) == pytest.approx(100 * 32768e-9 / 200e-9)
    assert read({**ctx, "rec": {"saves": []}}) is None
    assert read({**ctx, "trace": None}) is None
