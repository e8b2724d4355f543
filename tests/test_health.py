"""Health engine (ISSUE 10): SLO rules, stall watchdogs, critical-path
attribution, the health_query protocol surface, and the bbstat/bbtop CLI
exit codes.

Unit tests drive a private HealthEngine with hand-built snapshots and a
fake clock; the end-to-end test injects a stalled drain epoch and an
fsync slowdown into a live system's engine and reads the diagnosis back
through ``BurstBufferSystem.health()`` and ``bbtop --once --json``."""
import json
import os
import time

import pytest

from repro.core import health, telemetry
from repro.core.health import HealthConfig, HealthEngine
from repro.core.system import BBConfig, BurstBufferSystem
from tools import bbstat, bbtop


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class FakeTracer:
    """Duck-typed stand-in for telemetry.Tracer: a fixed list of finished
    spans, with ``events_total`` offset so the engine's lifetime watermark
    sees exactly these as fresh."""

    def __init__(self, events, base=0):
        self._events = list(events)
        self._base = base

    def events_total(self):
        return self._base + len(self._events)

    def events(self):
        return list(self._events)


def _engine(**cfg):
    return HealthEngine(HealthConfig(**cfg), clock=FakeClock())


def _slo(report, rule):
    return next(s for s in report["slos"] if s["rule"] == rule)


# --------------------------------------------------------------- SLO rules

def test_slo_burn_rate_window_flags_fresh_regression():
    """An hour of healthy history must not average away a fresh slowdown:
    the rule evaluates the p99 of *this window's* samples only."""
    reg = telemetry.Registry(clock=FakeClock())
    h = reg.histogram("ckpt.save_s")
    for _ in range(500):
        h.observe(1e-4)                     # long healthy history
    eng = _engine()
    r1 = eng.evaluate(reg.snapshot(), now=0.0)
    assert _slo(r1, "ckpt_save_p99")["verdict"] == "ok"
    for _ in range(10):
        h.observe(9.0)                      # fresh regression, tiny count
    r2 = eng.evaluate(reg.snapshot(), now=1.0)
    s = _slo(r2, "ckpt_save_p99")
    assert s["verdict"] == "critical"       # 10 samples vs 500 healthy
    assert s["value"] >= s["critical"]
    assert s["window_count"] == 10
    assert r2["status"] == "critical"
    # an idle window is not evidence either way
    r3 = eng.evaluate(reg.snapshot(), now=2.0)
    s = _slo(r3, "ckpt_save_p99")
    assert s["verdict"] == "ok" and s["value"] is None


def test_slo_worst_label_reported():
    reg = telemetry.Registry(clock=FakeClock())
    h = reg.histogram("store.fsync_s")
    for _ in range(10):
        h.observe(1e-3, label="sync")
        h.observe(3.0, label="spill")
    eng = _engine()
    s = _slo(eng.evaluate(reg.snapshot(), now=0.0), "fsync_p99")
    assert s["verdict"] == "critical" and s["label"] == "spill"


def test_slo_occupancy_ring_and_queue_depth_poll():
    snapshot = {
        "rings": {"server.occupancy": [
            [0.0, "server/0", 0.5], [1.0, "server/0", 0.99],
            [1.0, "server/1", 0.3]]},
        "polls": {"server.ops": {
            "server/0": {"queued_puts": 7},
            "server/1": {"queued_puts": 600}}},
    }
    r = _engine().evaluate(snapshot, now=0.0)
    occ = _slo(r, "occupancy")
    assert occ["verdict"] == "critical"     # last sample wins: 0.99
    assert occ["label"] == "server/0" and occ["value"] == 0.99
    qd = _slo(r, "queue_depth")
    assert qd["verdict"] == "warn" and qd["label"] == "server/1"
    assert qd["value"] == 600.0


# --------------------------------------------------------------- watchdogs

def test_epoch_stall_floor_and_adaptive_limit():
    eng = _engine()
    # young histogram: the floor is the limit
    r = eng.evaluate({}, inflight={"drain": {"epoch": 3, "started": 0.0}},
                     now=2.5)
    wd = [w for w in r["watchdogs"] if w["kind"] == "epoch_stall"]
    assert len(wd) == 1 and wd[0]["verdict"] == "critical"
    assert wd[0]["phase"] == "drain" and wd[0]["epoch"] == 3
    assert wd[0]["age_s"] == 2.5
    assert wd[0]["limit_s"] == pytest.approx(2.0)   # stall_floor_s
    # with drain history the limit adapts to stall_factor x p99
    reg = telemetry.Registry(clock=FakeClock())
    h = reg.histogram("manager.drain_epoch_s")
    for _ in range(20):
        h.observe(5.0)
    snap = reg.snapshot()
    r = eng.evaluate(snap, inflight={"drain": {"epoch": 4, "started": 0.0}},
                     now=20.0)
    assert not [w for w in r["watchdogs"] if w["kind"] == "epoch_stall"]
    r = eng.evaluate(snap, inflight={"drain": {"epoch": 4, "started": 0.0}},
                     now=60.0)
    wd = [w for w in r["watchdogs"] if w["kind"] == "epoch_stall"]
    assert len(wd) == 1
    assert wd[0]["limit_s"] > 30.0          # 4 x p99(~9.9s), not the floor
    # a closed epoch clears the anomaly
    r = eng.evaluate(snap, inflight={}, now=61.0)
    assert not [w for w in r["watchdogs"] if w["kind"] == "epoch_stall"]


def _src_msgs(**totals):
    return {"counters": {"transport.src_msgs": dict(totals)}}


def test_silent_server_fires_only_while_peers_advance():
    eng = _engine(silent_evals=2)
    seq = [
        _src_msgs(**{"server/0": 10, "server/1": 10, "client/0": 99}),
        _src_msgs(**{"server/0": 20, "server/1": 10}),   # s1 stalls (1)
        _src_msgs(**{"server/0": 30, "server/1": 10}),   # s1 stalls (2)
    ]
    for snap in seq[:-1]:
        r = eng.evaluate(snap, now=0.0)
        assert not [w for w in r["watchdogs"]
                    if w["kind"] == "silent_server"]
    r = eng.evaluate(seq[-1], now=0.0)
    wd = [w for w in r["watchdogs"] if w["kind"] == "silent_server"]
    assert len(wd) == 1 and wd[0]["server"] == "server/1"
    assert wd[0]["verdict"] == "critical"
    assert wd[0]["stalled_evals"] == 2
    # recovery: the counter advances again and the anomaly clears
    r = eng.evaluate(_src_msgs(**{"server/0": 40, "server/1": 11}), now=0.0)
    assert not [w for w in r["watchdogs"] if w["kind"] == "silent_server"]


def test_silent_server_idle_cluster_exempt():
    eng = _engine(silent_evals=1)
    snap = _src_msgs(**{"server/0": 10, "server/1": 10})
    for _ in range(5):                      # nobody advances: no asymmetry
        r = eng.evaluate(snap, now=0.0)
        assert not [w for w in r["watchdogs"]
                    if w["kind"] == "silent_server"]


def test_queue_growth_requires_strict_monotonic_run():
    eng = _engine(queue_growth_evals=3)

    def snap(depth):
        return {"polls": {"server.ops": {"server/0":
                                         {"queued_puts": depth}}}}
    for d in (1, 2, 3):                     # growing, but run too short
        r = eng.evaluate(snap(d), now=0.0)
        assert not [w for w in r["watchdogs"]
                    if w["kind"] == "queue_growth"]
    r = eng.evaluate(snap(4), now=0.0)      # 4th strictly-growing step
    wd = [w for w in r["watchdogs"] if w["kind"] == "queue_growth"]
    assert len(wd) == 1 and wd[0]["verdict"] == "warn"
    assert wd[0]["server"] == "server/0" and wd[0]["depth"] == 4
    r = eng.evaluate(snap(4), now=0.0)      # plateau resets the run
    assert not [w for w in r["watchdogs"] if w["kind"] == "queue_growth"]


def test_anomaly_transitions_counted_once():
    """A wedge held across many evaluations is one flight-recorder event
    and one counter increment, not a flood."""
    eng = _engine()
    before = telemetry.snapshot().get("counters", {}).get(
        "health.anomalies", {}).get("epoch_stall", 0)
    inflight = {"drain": {"epoch": 9, "started": 0.0}}
    for i in range(5):
        eng.evaluate({}, inflight=inflight, now=10.0 + i)
    after = telemetry.snapshot()["counters"]["health.anomalies"][
        "epoch_stall"]
    assert after == before + 1
    # clearing and re-firing is a second transition
    eng.evaluate({}, inflight={}, now=16.0)
    eng.evaluate({}, inflight=inflight, now=17.0)
    assert telemetry.snapshot()["counters"]["health.anomalies"][
        "epoch_stall"] == before + 2


# -------------------------------------------- critical-path attribution

def _ev(trace, span, parent, name, dur):
    return (trace, span, parent, name, "c", 0.0, dur, {})


def test_attribution_decomposes_and_names_dominant_segment():
    eng = _engine()
    tr = FakeTracer([
        _ev(1, 1, 0, "diag.save", 10.0),            # root
        _ev(1, 2, 1, "store.fsync", 6.1),           # fsync segment
        _ev(1, 3, 1, "client.lane_wait", 1.0),      # queue segment
    ])
    eng.evaluate({}, tracer=tr, now=0.0)            # ingest
    r = eng.evaluate({}, tracer=tr, now=1.0)        # settle + finalize
    op = r["bottlenecks"]["ops"]["diag.save"]
    assert op["count"] == 1 and op["dominant"] == "fsync"
    assert op["segments"]["fsync"]["share"] == pytest.approx(0.61)
    assert op["segments"]["queue"]["share"] == pytest.approx(0.10)
    # root self time is the gap no handler span covers: network
    assert op["segments"]["network"]["share"] == pytest.approx(0.29)
    assert op["segments"]["service"]["share"] == 0.0
    assert "fsync is 61% of diag.save" in op["summary"]
    top = r["bottlenecks"]["top"]
    assert top["op"] == "diag.save" and top["segment"] == "fsync"


def test_attribution_uncovered_root_time_is_network():
    eng = _engine()
    tr = FakeTracer([
        _ev(2, 1, 0, "diag.put", 10.0),
        _ev(2, 2, 1, "server.put", 4.0),    # only 4s instrumented
    ])
    eng.evaluate({}, tracer=tr, now=0.0)
    r = eng.evaluate({}, tracer=tr, now=1.0)
    op = r["bottlenecks"]["ops"]["diag.put"]
    assert op["dominant"] == "network"
    assert op["segments"]["network"]["share"] == pytest.approx(0.6)
    assert op["segments"]["service"]["share"] == pytest.approx(0.4)


def test_attribution_checkpoint_barrier_wait_is_network():
    """A save's wait for its replicated ACKs (``ckpt.barrier``) is time no
    handler covers, as the root's own; its submit loop is service."""
    eng = _engine()
    tr = FakeTracer([
        _ev(4, 1, 0, "ckpt.save", 10.0),
        _ev(4, 2, 1, "ckpt.pwrite", 4.0),
        _ev(4, 3, 1, "ckpt.barrier", 6.0),
    ])
    eng.evaluate({}, tracer=tr, now=0.0)
    r = eng.evaluate({}, tracer=tr, now=1.0)
    op = r["bottlenecks"]["ops"]["ckpt.save"]
    assert op["segments"]["network"]["share"] == pytest.approx(0.6)
    assert op["segments"]["service"]["share"] == pytest.approx(0.4)


def test_attribution_waits_for_straggler_spans():
    """A trace is attributed one evaluation after its last span lands, so
    spans finishing across threads between cadences still count."""
    eng = _engine()
    root = _ev(3, 1, 0, "diag.op", 10.0)
    late = _ev(3, 2, 1, "store.fsync", 9.0)
    tr = FakeTracer([root])
    eng.evaluate({}, tracer=tr, now=0.0)
    tr2 = FakeTracer([late], base=tr.events_total())
    r = eng.evaluate({}, tracer=tr2, now=1.0)       # straggler: re-touched
    assert "diag.op" not in r["bottlenecks"]["ops"]
    r = eng.evaluate({}, tracer=tr2, now=2.0)       # now settled
    assert r["bottlenecks"]["ops"]["diag.op"]["dominant"] == "fsync"


# ------------------------------------------------- end-to-end diagnosis

def test_end_to_end_diagnosis_and_bbtop(tmp_path, capsys):
    """Acceptance (ISSUE 10): with a fake clock, an injected stalled
    drain epoch and an injected fsync slowdown are both flagged within
    one evaluation, the critical path names fsync dominant for the
    affected op kind, and ``bbtop --once --json`` renders the same
    verdicts (exit code 4 on critical) from the health_query payload."""
    cfg = BBConfig(num_servers=1, num_clients=1, dram_capacity=4 << 20)
    cfg.health.interval_s = 3600.0          # park the run-loop evaluator
    sys_ = BurstBufferSystem(cfg).start()
    try:
        eng = sys_.manager._health
        assert eng is not None
        deadline = time.time() + 10.0       # run loop's baseline pass
        while eng._evals == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert eng._evals >= 1

        # inject: an fsync slowdown into the live registry...
        h = telemetry.histogram("store.fsync_s")
        for _ in range(50):
            h.observe(3.0, label="sync")
        # ...a drain epoch that has been open for 60 fake seconds...
        now = 1000.0
        inflight = {"drain": {"epoch": 7, "started": now - 60.0}}
        # ...and a span tree whose wall time is mostly fsync
        tr = FakeTracer([
            _ev(91, 1, 0, "diag.ckpt.save", 10.0),
            _ev(91, 2, 1, "store.fsync", 6.1),
            _ev(91, 3, 1, "client.lane_wait", 1.0),
        ], base=eng._events_seen)
        first = eng.evaluate(telemetry.snapshot(), inflight=inflight,
                             tracer=tr, now=now)
        # both faults flagged within ONE evaluation of being injected
        assert _slo(first, "fsync_p99")["verdict"] == "critical"
        assert [w for w in first["watchdogs"]
                if w["kind"] == "epoch_stall"]
        for _ in range(50):                 # slowdown persists into the
            h.observe(3.0, label="sync")    # next burn-rate window
        report = eng.evaluate(telemetry.snapshot(), inflight=inflight,
                              tracer=tr, now=now + 1.0)

        # (1) both injected faults flagged, within one evaluation each
        assert report["status"] == "critical"
        assert _slo(report, "fsync_p99")["verdict"] == "critical"
        stalls = [w for w in report["watchdogs"]
                  if w["kind"] == "epoch_stall"]
        assert stalls and stalls[0]["phase"] == "drain"
        # (2) the critical path names fsync dominant for the op kind
        op = report["bottlenecks"]["ops"]["diag.ckpt.save"]
        assert op["dominant"] == "fsync"
        assert op["segments"]["fsync"]["share"] == pytest.approx(0.61)

        # the protocol surface carries the same report
        via_query = sys_.health()
        assert via_query["status"] == "critical"
        assert via_query["evals"] == report["evals"]
        assert telemetry.TRACE_KEY not in via_query
        assert [s["verdict"] for s in via_query["slos"]] == \
            [s["verdict"] for s in report["slos"]]
        # ...and rides pressure_report for the drain engine's consumers
        assert sys_.pressure()["health"]["status"] == "critical"

        # bbtop --once --json renders the same verdicts, exit code 4
        doc = tmp_path / "health.json"
        doc.write_text(json.dumps(via_query))
        capsys.readouterr()
        rc = bbtop.main([str(doc), "--once", "--json"])
        frame = json.loads(capsys.readouterr().out)
        assert rc == 4
        assert frame["health"]["status"] == "critical"
        assert frame["health"]["bottlenecks"]["ops"][
            "diag.ckpt.save"]["dominant"] == "fsync"
        # human rendering of the same frame survives too
        assert bbtop.main([str(doc), "--once"]) == 4
        out = capsys.readouterr().out
        assert "status=CRITICAL" in out
        assert "fsync is 61% of diag.ckpt.save" in out
    finally:
        sys_.stop()


def test_health_query_one_server_cluster():
    sys_ = BurstBufferSystem(BBConfig(num_servers=1, num_clients=1,
                                      dram_capacity=4 << 20)).start()
    try:
        r = sys_.transport.request(
            sys_.clients[0].ep, "manager", "health_query", {},
            timeout=sys_.cfg.control_timeout)
        assert r is not None and r.kind == "health"
        for key in ("status", "evals", "slos", "watchdogs", "bottlenecks"):
            assert key in r.payload
        h = sys_.health()
        assert h["status"] in ("ok", "warn", "critical")
        assert {s["rule"] for s in h["slos"]} == \
            {rule[0] for rule in health.SLO_RULES}
    finally:
        sys_.stop()


def test_health_disabled_zero_overhead(monkeypatch):
    """With telemetry off the manager holds no engine at all and the
    query surface answers a static stub — no evaluator on the run loop."""
    monkeypatch.setattr(telemetry, "_registry", None)
    sys_ = BurstBufferSystem(BBConfig(num_servers=1, num_clients=1,
                                      dram_capacity=4 << 20)).start()
    try:
        assert sys_.manager._health is None
        h = sys_.health()
        assert h["status"] == "disabled" and h["evals"] == 0
        assert sys_.pressure()["health"]["status"] == "disabled"
    finally:
        sys_.stop()


# ------------------------------------------------- scrape vs dead server

def test_scrape_reports_killed_server_and_bbstat_exits_3(tmp_path, capsys):
    sys_ = BurstBufferSystem(BBConfig(num_servers=3, num_clients=1,
                                      dram_capacity=4 << 20)).start()
    try:
        f = sys_.fs().open("hk/data", "w", policy="batched")
        chunk = os.urandom(64 << 10)
        for i in range(8):
            f.pwrite(chunk, i * len(chunk))
        f.close(30.0)
        sys_.kill_server("server/1")
        t0 = time.time()
        doc = sys_.scrape()
        elapsed = time.time() - t0
        # dead server skipped via alive(), never awaited: bounded well
        # under the per-survivor control_timeout budget
        assert elapsed < sys_.cfg.control_timeout * len(sys_.servers)
        assert doc["expected"] == ["server/0", "server/1", "server/2"]
        assert doc["missing"] == ["server/1"]
        assert set(doc["servers"]) == {"server/0", "server/2"}
        # the partial scrape fails loud in bbstat, in both entrypoints
        assert bbstat.check_missing(doc) == 3
        assert "server/1" in capsys.readouterr().out
        path = tmp_path / "scrape.json"
        path.write_text(json.dumps(doc, default=repr))
        assert bbstat.main([str(path)]) == 3
        assert "MISSING servers: server/1" in capsys.readouterr().out
    finally:
        sys_.stop()


def test_bbstat_missing_exit_code_paths(capsys):
    # healthy scrape: exit 0
    healthy = {"expected": ["server/0"], "servers": {"server/0": {}},
               "missing": []}
    assert bbstat.check_missing(healthy) == 0
    # pre-ISSUE-10 document without membership fields passes vacuously
    assert bbstat.check_missing({"registry": {}}) == 0
    # fallback: expected minus answering set when "missing" is absent
    legacy = {"expected": ["server/0", "server/1"],
              "servers": {"server/0": {}}}
    assert bbstat.check_missing(legacy) == 3
    assert "server/1" in capsys.readouterr().out


def test_bbtop_accepts_all_document_shapes():
    bare = {"status": "ok", "evals": 1, "t": 0.0, "slos": [],
            "watchdogs": [], "bottlenecks": {"ops": {}, "top": None}}
    assert bbtop.as_frame(bare)["health"] is bare
    pressure = {"health": bare, "servers": {"server/0": {"fraction": 0.5}}}
    frame = bbtop.as_frame(pressure)
    assert frame["health"] is bare
    assert frame["pressure"]["servers"]["server/0"]["fraction"] == 0.5
    wrapped = {"health": bare, "pressure": None}
    assert bbtop.as_frame(wrapped)["health"] is bare
    with pytest.raises(ValueError):
        bbtop.as_frame({"registry": {}})
