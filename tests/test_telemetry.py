"""Telemetry (ISSUE 9): instruments, causal tracing, flight recorder.

Unit tests drive a private Registry with a fake clock; integration tests
lean on the session-wide registry conftest enables (filtering tracer
events by trace id, so parallel history from other tests never bleeds
in)."""
import json
import os
import threading
import time

import numpy as np
import pytest

import tools.bbcheck.metrics as metrics_doc
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.core import telemetry
from repro.core.drain import DrainConfig
from repro.core.system import BBConfig, BurstBufferSystem


# ------------------------------------------------------------- instruments

class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def test_counter_gauge_units():
    reg = telemetry.Registry(clock=FakeClock())
    c = reg.counter("transport.msgs")
    c.inc(label="put")
    c.inc(3, label="put")
    c.add(2)
    assert c.snapshot() == {"put": 4, "": 2}
    g = reg.gauge("qos.occupancy_ewma")
    g.set(0.25, label="c0")
    g.set(0.75, label="c0")
    assert g.snapshot() == {"c0": 0.75}


def test_histogram_buckets_and_stats():
    reg = telemetry.Registry(clock=FakeClock())
    h = reg.histogram("ckpt.save_s")
    for v in (5e-6, 2e-3, 2e-3, 0.5, 99.0):    # 99s lands in overflow
        h.observe(v)
    snap = h.snapshot()
    st = snap["series"][""]
    assert st["count"] == 5
    assert st["min"] == 5e-6 and st["max"] == 99.0
    assert st["sum"] == pytest.approx(5e-6 + 2e-3 + 2e-3 + 0.5 + 99.0)
    assert len(st["buckets"]) == len(snap["bounds"]) + 1
    assert sum(st["buckets"]) == 5
    assert st["buckets"][0] == 1          # 5us < first bound (10us)
    assert st["buckets"][-1] == 1         # overflow
    # 2ms falls in the (1e-3, 3.16e-3] bucket
    idx = snap["bounds"].index(3.16e-3)
    assert st["buckets"][idx] == 2


def test_ring_bounded_and_clock_stamped():
    clock = FakeClock()
    reg = telemetry.Registry(clock=clock)
    r = reg.ring("server.occupancy")
    for i in range(telemetry.Ring.MAXLEN + 10):
        clock.t = 100.0 + i
        r.note(i / 1000.0, label="s0")
    snap = r.snapshot()
    assert len(snap) == telemetry.Ring.MAXLEN      # oldest 10 dropped
    assert snap[0][0] == 110.0 and snap[0][1] == "s0"
    assert snap[-1][2] == pytest.approx(
        (telemetry.Ring.MAXLEN + 9) / 1000.0)


def test_unknown_instrument_rejected():
    reg = telemetry.Registry(clock=FakeClock())
    with pytest.raises(ValueError, match="CATALOG"):
        reg.counter("nope.not_declared")
    with pytest.raises(ValueError, match="CATALOG"):
        reg.histogram("transport.msgs")     # declared, but as a counter
    with pytest.raises(ValueError, match="CATALOG"):
        reg.poll("nope.poll", dict)


def test_poll_replacement_and_snapshot():
    reg = telemetry.Registry(clock=FakeClock())
    reg.poll("client.ops", lambda: {"puts": 1}, label="c0")
    reg.poll("client.ops", lambda: {"puts": 7}, label="c0")   # replaces
    reg.poll("client.ops", lambda: 1 / 0, label="dead")       # skipped
    snap = reg.snapshot()
    assert snap["polls"]["client.ops"] == {"c0": {"puts": 7}}


def test_disabled_module_api_is_noop(monkeypatch):
    monkeypatch.setattr(telemetry, "_registry", None)
    assert not telemetry.enabled()
    assert telemetry.counter("transport.msgs") is telemetry.NOOP
    assert telemetry.histogram("ckpt.save_s") is telemetry.NOOP
    assert telemetry.span("x") is telemetry.NOOP
    assert telemetry.msg_span("x", "c", {"_trace": [1, 2]}) is telemetry.NOOP
    assert telemetry.snapshot() == {}
    p = {"k": 1}
    assert telemetry.trace_inject(p) is p and "_trace" not in p
    telemetry.record("c", "event")          # swallowed, no crash


def test_registry_thread_safety_hammer():
    reg = telemetry.Registry(clock=time.monotonic)
    c = reg.counter("transport.msgs")
    h = reg.histogram("server.dispatch_s")
    n_threads, n_iter = 8, 500
    errors = []

    def hammer(i):
        try:
            for j in range(n_iter):
                c.inc(label=f"t{i % 4}")
                h.observe(j * 1e-6, label=f"t{i % 4}")
                if j % 100 == 0:
                    reg.snapshot()
        except Exception as e:      # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=hammer, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    assert sum(c.snapshot().values()) == n_threads * n_iter
    hs = reg.histogram("server.dispatch_s").snapshot()["series"]
    assert sum(st["count"] for st in hs.values()) == n_threads * n_iter


# ----------------------------------------------------------------- tracing

def test_span_tree_and_chrome_export(tmp_path):
    clock = FakeClock()
    reg = telemetry.Registry(clock=clock)
    with reg.tracer.root("op", "app", step=7) as root:
        ctx = reg.tracer.current_ctx()
        assert ctx == [root.trace_id, root.span_id]
        with reg.tracer.span("child", "worker"):
            clock.t += 0.5
    events = reg.tracer.events()
    assert len(events) == 2
    (child, parent) = events         # child finishes first
    assert child[3] == "child" and parent[3] == "op"
    assert child[0] == parent[0]               # same trace
    assert child[2] == parent[1]               # parented by root
    chrome = reg.tracer.chrome_events()
    xs = [e for e in chrome if e["ph"] == "X"]
    metas = [e for e in chrome if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"op", "child"}
    assert {m["args"]["name"] for m in metas} == {"app", "worker"}
    assert xs[0]["dur"] == pytest.approx(0.5e6)    # microseconds


class _Annotation:
    """A stand-in for the profiler's annotation: logs its life."""

    log = []

    def __init__(self, name, **args):
        self.name = name
        self.log.append(("new", name, args))

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def annotations(monkeypatch):
    """Telemetry off, with a logging annotator as its profiler sink."""
    log = []
    monkeypatch.setattr(_Annotation, "log", log)
    monkeypatch.setattr(telemetry, "_registry", None)
    monkeypatch.setattr(telemetry, "_annotator", _Annotation)
    monkeypatch.setattr(telemetry, "_on_enable", None)
    monkeypatch.setattr(telemetry, "_on_disable", None)
    return log


def test_annotator_wraps_each_live_span_in_order(annotations, monkeypatch):
    monkeypatch.setattr(telemetry, "_registry",
                        telemetry.Registry(clock=FakeClock()))
    with telemetry.span("op", "app", step=4):
        with telemetry.child_span("leaf", "app", step=4, leaf="w"):
            assert annotations[-1] == ("enter", "leaf")
        telemetry.observe_span("wait", "app", telemetry.current_ctx(),
                               100.0, 0.5)
        telemetry.observe_child("jax.compile", "jax", 0.25)
    assert annotations == [
        ("new", "op", {"step": 4}), ("enter", "op"),
        ("new", "leaf", {"step": 4, "leaf": "w"}), ("enter", "leaf"),
        ("exit", "leaf"), ("exit", "op")]
    names = [e[3] for e in telemetry.registry().tracer.events()]
    assert names == ["leaf", "wait", "jax.compile", "op"]


def test_annotator_never_called_with_telemetry_off(annotations):
    with telemetry.span("op", "app", step=1):
        with telemetry.child_span("leaf", "app"):
            pass
    telemetry.observe_span("wait", "app", [1, 2], 0.0, 1.0)
    telemetry.observe_child("jax.compile", "jax", 0.25)
    assert annotations == []
    assert telemetry.current_span() is None


def test_profiler_sink_hooks_follow_enable_and_disable(annotations,
                                                      monkeypatch):
    calls = []
    telemetry.set_profiler_sink(_Annotation, lambda: calls.append("on"),
                                lambda: calls.append("off"))
    assert calls == []                      # off: nothing attached yet
    telemetry.enable()
    telemetry.enable()                      # idempotent: attached once
    assert calls == ["on"]
    telemetry.set_profiler_sink(_Annotation, lambda: calls.append("on2"))
    assert calls == ["on", "off", "on2"]    # swapped while enabled
    telemetry.disable()
    assert calls == ["on", "off", "on2"]    # the new sink has no detach


def test_observe_child_takes_the_innermost_span_step_and_name(monkeypatch):
    reg = telemetry.Registry(clock=FakeClock(50.0))
    monkeypatch.setattr(telemetry, "_registry", reg)
    with reg.tracer.root("ckpt.save", "checkpoint", step=9) as root:
        assert telemetry.current_span() is root
        telemetry.observe_child("jax.compile", "jax", 2.0)
    (compile_, _) = reg.tracer.events()
    trace, _, parent, name, comp, t0, dur, args = compile_
    assert (trace, parent) == (root.trace_id, root.span_id)
    assert (name, comp, t0, dur) == ("jax.compile", "jax", 48.0, 2.0)
    assert args == {"step": 9, "in": "ckpt.save"}


def test_untraced_message_costs_nothing():
    reg = telemetry.Registry(clock=FakeClock())
    # no message context, no active span: msg_span refuses to open a root
    assert reg.tracer.span("s", "c") is telemetry.NOOP
    assert reg.tracer.events() == []


def _trace_components(trace_id):
    comps = set()
    for e in telemetry.export_chrome():
        if e.get("ph") == "X" and e["args"]["trace"] == trace_id:
            comps.add(e["cat"])
    return comps


def _trace_names(trace_id):
    names = set()
    for e in telemetry.export_chrome():
        if e.get("ph") == "X" and e["args"]["trace"] == trace_id:
            names.add(e["name"])
    return names


def test_put_trace_crosses_client_server_replica():
    sys_ = BurstBufferSystem(BBConfig(num_servers=3, num_clients=1,
                                      replication=2)).start()
    try:
        cli = sys_.clients[0]
        with telemetry.span("test.put", "test") as root:
            trace = root.trace_id
            cli.put("k1", b"x" * 1024)
        cli.drain(5.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            comps = _trace_components(trace)
            if sum(1 for c in comps if c.startswith("server/")) >= 2 \
                    and any(c.startswith("client/") for c in comps):
                break
            time.sleep(0.05)
        comps = _trace_components(trace)
        # primary + replica hop + client-side ack processing, one trace
        assert sum(1 for c in comps if c.startswith("server/")) >= 2, comps
        assert any(c.startswith("client/") for c in comps), comps
        names = _trace_names(trace)
        assert "server.put" in names or "server.put_batch" in names, names
        assert "server.replica_put" in names \
            or "server.replica_put_batch" in names, names
    finally:
        sys_.stop()


def test_drain_epoch_trace_crosses_server_and_manager():
    dk = dict(high_watermark=0.5, low_watermark=0.25,
              request_interval=0.02, pressure_interval=0.05,
              max_epoch_bytes=2 << 20, epoch_timeout_s=5.0)
    sys_ = BurstBufferSystem(BBConfig(
        num_servers=3, num_clients=3, placement="iso",
        dram_capacity=1 << 20, ssd_capacity=2 << 20,
        segment_bytes=128 << 10, chunk_bytes=64 << 10,
        drain=DrainConfig(**dk))).start()
    try:
        data = np.random.default_rng(0).integers(
            0, 256, 6 << 20, dtype=np.uint8).tobytes()
        f = sys_.fs().open("big", "w", policy="batched")
        f.pwrite(data, 0)
        f.close(60.0)
        deadline = time.monotonic() + 20.0
        roots = []
        while time.monotonic() < deadline:
            roots = [e for e in telemetry.export_chrome()
                     if e.get("ph") == "X"
                     and e["name"] == "server.drain_request"]
            done = [r for r in roots
                    if "manager.drain_request"
                    in _trace_names(r["args"]["trace"])]
            if done:
                roots = done
                break
            time.sleep(0.1)
        assert roots, "no drain_request trace recorded"
        comps = _trace_components(roots[0]["args"]["trace"])
        assert "manager" in comps, comps
        assert any(c.startswith("server/") for c in comps), comps
    finally:
        sys_.stop()


def test_ckpt_save_trace_spans_three_components():
    """Acceptance: one bbckpt.save() produces a Chrome trace whose span
    tree crosses >= 3 components (client, server, manager)."""
    sys_ = BurstBufferSystem(BBConfig(num_servers=3, num_clients=2,
                                      dram_capacity=4 << 20)).start()
    try:
        ck = BBCheckpointManager(sys_, io_mode="batched")
        state = {"w": np.arange(1 << 16, dtype=np.float32)}
        ck.save(1, state, blocking_flush=True)
        saves = [e for e in telemetry.export_chrome()
                 if e.get("ph") == "X" and e["name"] == "ckpt.save"]
        assert saves
        comps = _trace_components(saves[-1]["args"]["trace"])
        assert "checkpoint" in comps
        assert any(c.startswith("client/") for c in comps), comps
        assert any(c.startswith("server/") for c in comps), comps
        assert "manager" in comps, comps
        assert len(comps) >= 3
    finally:
        sys_.stop()


# ----------------------------------------------------------------- scrape

def test_scrape_and_metrics_query():
    sys_ = BurstBufferSystem(BBConfig(num_servers=3, num_clients=2,
                                      dram_capacity=4 << 20)).start()
    try:
        f = sys_.fs().open("scr/data", "w", policy="batched",
                           lane="checkpoint")
        chunk = os.urandom(64 << 10)
        for i in range(16):
            f.pwrite(chunk, i * len(chunk))
        f.close(30.0)
        scrape = sys_.scrape()
        reg = scrape["registry"]
        lw = reg["histograms"]["client.lane_wait_s"]["series"]
        assert sum(st["count"] for st in lw.values()) > 0
        assert sum(reg["counters"]["transport.msgs"].values()) > 0
        assert scrape["servers"], "no server answered metrics_query"
        for payload in scrape["servers"].values():
            assert "stats" in payload and "puts" in payload["stats"]
        # remote-scraper path: instruments ride the reply when asked
        probe = sys_.clients[0]
        r = sys_.transport.request(
            probe.ep, next(iter(sys_.servers)), "metrics_query",
            {"instruments": True}, timeout=2.0)
        assert r is not None and r.kind == "metrics"
        assert "histograms" in r.payload["instruments"]
    finally:
        sys_.stop()


def test_spill_fsync_histograms_under_pressure():
    sys_ = BurstBufferSystem(BBConfig(
        num_servers=2, num_clients=2, dram_capacity=256 << 10,
        segment_bytes=64 << 10, chunk_bytes=32 << 10,
        drain=DrainConfig(enabled=False))).start()
    try:
        f = sys_.fs().open("press/data", "w", policy="batched")
        chunk = os.urandom(64 << 10)
        for i in range(24):                     # 1.5MB >> 512KB DRAM
            f.pwrite(chunk, i * len(chunk))
        f.close(30.0)
        reg = telemetry.snapshot()
        spill = reg["histograms"].get("store.spill_s", {"series": {}})
        fsync = reg["histograms"].get("store.fsync_s", {"series": {}})
        assert sum(st["count"] for st in spill["series"].values()) > 0
        assert sum(st["count"] for st in fsync["series"].values()) > 0
    finally:
        sys_.stop()


# --------------------------------------------------------- flight recorder

def test_flight_recorder_round_trip(tmp_path):
    clock = FakeClock()
    reg = telemetry.Registry(clock=clock)
    for i in range(telemetry.FlightRecorder.PER_COMPONENT + 5):
        reg.flight.record("server/0", "redirect", n=i)
    reg.flight.record("manager", "drain_abort", reason="timeout")
    path = reg.flight.dump(str(tmp_path / "flight.json"), test="t1")
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["test"] == "t1"
    ring = doc["flight"]["server/0"]
    assert len(ring) == telemetry.FlightRecorder.PER_COMPONENT  # bounded
    assert ring[-1]["n"] == telemetry.FlightRecorder.PER_COMPONENT + 4
    assert ring[0]["n"] == 5                                    # oldest cut
    assert doc["flight"]["manager"][0]["event"] == "drain_abort"
    assert doc["flight"]["manager"][0]["t"] == 100.0


def test_dump_flight_disabled_still_writes(tmp_path, monkeypatch):
    monkeypatch.setattr(telemetry, "_registry", None)
    path = telemetry.dump_flight(str(tmp_path / "empty.json"), test="t2")
    with open(path) as fh:
        doc = json.load(fh)
    assert doc == {"flight": {}, "test": "t2"}


# -------------------------------------------------------------------- docs

def test_metrics_doc_in_sync():
    """docs/METRICS.md must match telemetry.CATALOG byte-for-byte (the
    --lint drift gate, mirrored as a test)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "docs", "METRICS.md")) as fh:
        committed = fh.read()
    assert committed == metrics_doc.render(), \
        "regenerate with `python -m tools.bbcheck --emit-metrics " \
        "docs/METRICS.md`"


def test_catalog_sorted_and_unique():
    names = [spec[0] for spec in telemetry.CATALOG]
    assert names == sorted(names)
    assert len(names) == len(set(names))
    assert all(spec[1] in ("counter", "gauge", "histogram", "ring", "poll",
                           "span")
               for spec in telemetry.CATALOG)
