"""The checkpoint layer's spans, on the profiler's clock.

``core/telemetry`` imports no JAX. Importing this module installs JAX's
pieces into it as its profiler sink:

- ``jax.profiler.TraceAnnotation`` as the annotator: every live span is also
  a host annotation of a profiler trace, on the thread that opened it, so a
  device trace's idle gaps can be named after the program's spans;
- while telemetry is enabled, one ``jax.monitoring`` listener that records
  each backend compile (or persistent-cache load) as a completed
  ``jax.compile`` span under the compiling thread's innermost open span
  (with its ``step`` and name, ``in``) and counts it in ``jax.compiles``.

``root`` opens a checkpoint root span that records ``rss_peak_bytes``, the
process's resident peak while it was open; ``leaf`` opens a child span for
one leaf, with the ``step`` of the span it opens under. On save these are
``ckpt.quantize`` (an int8 leaf quantized on the device, closed once its
results are ready) and ``ckpt.fetch`` (a leaf's device-to-host copies, whose
``bytes`` are the payload that crossed the link). With telemetry disabled
both return ``telemetry.NOOP`` and nothing else is made.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from repro.core import telemetry

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# per thread: the largest VmRSS read at a leaf span's exit inside the open
# root, where the kernel's resident peak cannot be reset; None otherwise
_sampled = threading.local()


def _on_duration(event: str, duration_secs: float, **_):
    if event != COMPILE_EVENT:
        return
    cur = telemetry.current_span()
    telemetry.counter("jax.compiles").inc(
        label=cur.name if cur is not None else "")
    telemetry.observe_child("jax.compile", "jax", duration_secs)


def _attach():
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _detach():
    jax.monitoring.unregister_event_duration_listener(_on_duration)


telemetry.set_profiler_sink(jax.profiler.TraceAnnotation, _attach, _detach)


# ----------------------------------------------------------- host memory
def _status_bytes(field: str) -> Optional[int]:
    """A ``kB`` field of ``/proc/self/status`` in bytes; None off Linux."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def _reset_peak() -> bool:
    """Reset the process's resident peak (``VmHWM``); False where the
    kernel does not allow it."""
    try:
        with open("/proc/self/clear_refs", "w") as fh:
            fh.write("5")
        return True
    except OSError:
        return False


class _Root:
    """A root span that records the resident peak while it is open: the
    kernel's ``VmHWM`` reset at entry and read at exit, else the largest
    ``VmRSS`` read at its leaf spans' exits and its own."""

    __slots__ = ("span", "hwm")

    def __init__(self, span):
        self.span = span
        self.hwm = False

    def __enter__(self):
        self.span.__enter__()
        self.hwm = _reset_peak()
        _sampled.peak = None if self.hwm else 0
        return self.span

    def __exit__(self, *exc):
        if self.hwm:
            peak = _status_bytes("VmHWM")
        else:
            peak = max(_sampled.peak or 0, _status_bytes("VmRSS") or 0)
        _sampled.peak = None
        if peak:
            self.span.args["rss_peak_bytes"] = peak
        return self.span.__exit__(*exc)


class _Sampled:
    """A leaf span that reads ``VmRSS`` at its exit for its root."""

    __slots__ = ("span",)

    def __init__(self, span):
        self.span = span

    def __enter__(self):
        return self.span.__enter__()

    def __exit__(self, *exc):
        _sampled.peak = max(_sampled.peak or 0, _status_bytes("VmRSS") or 0)
        return self.span.__exit__(*exc)


def root(name: str, **args):
    """A checkpoint span (a root where no span is open on this thread)
    that records ``rss_peak_bytes``."""
    sp = telemetry.span(name, "checkpoint", **args)
    return sp if sp is telemetry.NOOP else _Root(sp)


def leaf(name: str, leaf_name: str, nbytes: int):
    """A child span for one leaf, with the ``step`` of the span it opens
    under; untraced where no span is open."""
    top = telemetry.current_span()
    if top is None:
        return telemetry.NOOP
    sp = telemetry.child_span(name, "checkpoint", step=top.args.get("step"),
                              leaf=leaf_name, bytes=nbytes)
    if getattr(_sampled, "peak", None) is None:
        return sp
    return _Sampled(sp)
