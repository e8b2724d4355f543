"""The checkpoint layer's spans: a save is ``ckpt.serialize`` (each int8
leaf's ``ckpt.quantize`` on the device, then each leaf's ``ckpt.fetch`` of
its payload) and ``ckpt.save`` (``ckpt.pwrite``,
``ckpt.barrier``); a restore is ``ckpt.restore`` (``ckpt.stage``,
``ckpt.read``, each leaf's ``ckpt.dequantize`` and ``ckpt.place``); compiles
inside them are ``jax.compile``; the flush says whether it was acked.

Runs under the session-wide telemetry that conftest enables; events are
taken from the tracer's tail after a watermark."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import serializer as ser
from repro.checkpoint import tracing
from repro.checkpoint.bbckpt import BBCheckpointManager
from repro.core import BBConfig, BurstBufferSystem, telemetry

CKPT = ("ckpt.serialize", "ckpt.fetch", "ckpt.quantize", "ckpt.save",
        "ckpt.pwrite", "ckpt.barrier", "ckpt.flush", "ckpt.stage",
        "ckpt.read", "ckpt.dequantize", "ckpt.place", "ckpt.restore")


def _tree(shape=(64, 48)):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    return {"params": {"w": jax.random.normal(k[0], shape, jnp.bfloat16),
                       "b": jax.random.normal(k[1], (48,), jnp.float32)},
            "opt_state": {"m": jax.random.normal(k[2], shape, jnp.float32),
                          "step": jnp.asarray(5, jnp.int32)}}


@pytest.fixture
def system():
    sys_ = BurstBufferSystem(BBConfig(num_servers=3, num_clients=2,
                                      dram_capacity=8 << 20)).start()
    yield sys_
    sys_.stop()


def _events_since(n0):
    tr = telemetry.registry().tracer
    fresh = tr.events_total() - n0
    return tr.events()[-fresh:] if fresh > 0 else []


def _ckpt_events(events, step):
    return [e for e in events
            if e[3] in CKPT + ("jax.compile",) and e[7].get("step") == step]


def _save_restore(system, step, tree):
    assert telemetry.enabled()
    n0 = telemetry.registry().tracer.events_total()
    ck = BBCheckpointManager(system, quantize=True)
    ck.save(step, tree)
    ck.wait_flushes()
    ck.restore(tree, step)
    return _ckpt_events(_events_since(n0), step)


def test_save_and_restore_split_into_leaf_spans(system):
    tree = _tree()
    evs = _save_restore(system, 41, tree)
    names = [n for n, _ in ser.tree_paths(tree)]
    int8 = [n for n, l in ser.tree_paths(tree)
            if ser.default_quant_policy(n, l)]
    assert int8 == ["opt_state/m"]

    def leaves(name):
        return sorted(e[7]["leaf"] for e in evs if e[3] == name)

    assert leaves("ckpt.fetch") == sorted(names)
    assert leaves("ckpt.quantize") == int8
    assert leaves("ckpt.dequantize") == int8
    assert leaves("ckpt.place") == sorted(names)
    for e in evs:
        if e[3] in ("ckpt.fetch", "ckpt.quantize", "ckpt.dequantize",
                    "ckpt.place"):
            assert e[7]["bytes"] > 0, e
    fetched = {e[7]["leaf"]: e[7]["bytes"] for e in evs
               if e[3] == "ckpt.fetch"}
    assert fetched["params/w"] == 64 * 48 * 2
    # an int8 leaf fetches its payload, once quantized on the device
    leaf = tree["opt_state"]["m"]
    assert fetched["opt_state/m"] == ser.leaf_nbytes(leaf, True)
    (quant,) = [e for e in evs if e[3] == "ckpt.quantize"]
    (fetch,) = [e for e in evs if e[3] == "ckpt.fetch"
                and e[7]["leaf"] == "opt_state/m"]
    assert quant[5] + quant[6] <= fetch[5]
    # every checkpoint span carries the step; the roots stay roots
    by_id = {e[1]: e for e in evs}
    roots = sorted(e[3] for e in evs if e[2] == 0)
    assert roots == ["ckpt.flush", "ckpt.restore", "ckpt.save",
                     "ckpt.serialize"]
    parent_of = {e[3]: by_id[e[2]][3] for e in evs
                 if e[2] in by_id and e[3] != "jax.compile"}
    assert parent_of == {
        "ckpt.fetch": "ckpt.serialize", "ckpt.quantize": "ckpt.serialize",
        "ckpt.pwrite": "ckpt.save", "ckpt.barrier": "ckpt.save",
        "ckpt.stage": "ckpt.restore", "ckpt.read": "ckpt.restore",
        "ckpt.dequantize": "ckpt.restore", "ckpt.place": "ckpt.restore"}
    # children, run one after another on the caller's thread, sum to no
    # more than their parent
    for pid, parent in by_id.items():
        kids = [e[6] for e in evs if e[2] == pid]
        assert sum(kids) <= parent[6] + 1e-9, parent[3]
    for e in evs:
        if e[3] in ("ckpt.serialize", "ckpt.save", "ckpt.restore"):
            assert e[7]["rss_peak_bytes"] > 1 << 20, e


def test_flush_records_its_ack_and_the_manager_answers(system,
                                                       monkeypatch):
    ck = BBCheckpointManager(system)
    n0 = telemetry.registry().tracer.events_total()
    assert ck.flush_acked(12) is None
    ck.save(12, {"w": np.arange(4096, dtype=np.float32)})
    ck.wait_flushes()
    assert ck.flush_acked(12) is True
    (flush,) = [e for e in _events_since(n0)
                if e[3] == "ckpt.flush" and e[7]["step"] == 12]
    assert flush[7]["acked"] is True
    assert flush[7]["bytes"] == ck.metrics[12]["bytes"] == 4096 * 4

    monkeypatch.setattr(system, "flush", lambda epoch, timeout: False)
    ck.save(13, {"w": np.arange(4096, dtype=np.float32)})
    with pytest.raises(TimeoutError):
        ck.wait_flushes()
    assert ck.flush_acked(13) is False
    (flush,) = [e for e in _events_since(n0)
                if e[3] == "ckpt.flush" and e[7]["step"] == 13]
    assert flush[7]["acked"] is False


def test_a_compile_inside_a_save_is_a_span_with_its_step(system):
    counter = telemetry.registry().counter("jax.compiles")
    before = counter.snapshot().get("ckpt.quantize", 0)
    # a leaf shape no other test uses: its quantize programs are new
    evs = _save_restore(system, 57, _tree((37, 211)))
    compiles = [e for e in evs if e[3] == "jax.compile"]
    # the flatten of the new shape compiles inside the quantize span (its
    # padded size may already be compiled elsewhere in the process)
    assert "ckpt.quantize" in {e[7]["in"] for e in compiles}
    by_id = {e[1]: e for e in evs}
    for e in compiles:
        assert e[7]["step"] == 57 and e[4] == "jax"
        assert by_id[e[2]][3] == e[7]["in"]
        assert 0 < e[6] <= by_id[e[2]][6]
    assert counter.snapshot()["ckpt.quantize"] > before


def test_resident_peak_falls_back_to_the_leaves_reads(system, monkeypatch):
    monkeypatch.setattr(tracing, "_reset_peak", lambda: False)
    evs = _save_restore(system, 63, _tree())
    for e in evs:
        if e[3] in ("ckpt.serialize", "ckpt.save", "ckpt.restore"):
            assert e[7]["rss_peak_bytes"] > 1 << 20, e


def test_no_listener_or_span_with_telemetry_off():
    from jax._src import monitoring
    assert tracing._on_duration in \
        monitoring.get_event_duration_listeners()
    telemetry.disable()
    try:
        assert tracing._on_duration not in \
            monitoring.get_event_duration_listeners()
        assert tracing.root("ckpt.save", step=1) is telemetry.NOOP
        assert tracing.leaf("ckpt.fetch", "w", 4) is telemetry.NOOP
        payloads, manifest = ser.serialize_tree(_tree())
        assert manifest["total_bytes"] > 0
    finally:
        telemetry.enable()
    assert tracing._on_duration in \
        monitoring.get_event_duration_listeners()
