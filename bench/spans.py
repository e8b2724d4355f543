"""The program's own spans, read two ways.

From the telemetry records the harness collects in a traced run
(``ctx["spans"]``: name, seconds, args), per save or per resume of the
window: the per-layer metrics that split a save and a restore into their
parts read them through ``per_save`` and ``per_resume``.

From a profiler trace, where each live span of the program is also a host
annotation on the thread that opened it (``repro.checkpoint.tracing``):
``load`` collects the spans named ``ckpt.*`` and ``jax.*``, those on the
thread lines that hold the benchmark's ``bench.*`` marks apart from those on
the program's other threads (client, server, manager, flush). ``idle_gaps``
names each gap after the innermost mark or span of the marks' threads that
covers it; ``idle_inside`` gives the device's idle time inside one kind of
mark while a span of another thread is open.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

from bench import trace
from bench.trace import Interval

PROGRAM_PREFIXES = ("ckpt.", "jax.")

# the spans of one save (two roots that share its step) and of one restore
SAVE = ("ckpt.serialize", "ckpt.fetch", "ckpt.quantize", "ckpt.save",
        "ckpt.pwrite", "ckpt.barrier")
RESTORE = ("ckpt.restore", "ckpt.stage", "ckpt.read", "ckpt.dequantize",
           "ckpt.place")


# ------------------------------------------------- telemetry records


def named(*names: str) -> Callable[[dict], bool]:
    return lambda s: s["name"] in names


def compiles_in(names) -> Callable[[dict], bool]:
    """``jax.compile`` spans recorded inside one of the spans ``names``."""
    return lambda s: s["name"] == "jax.compile" \
        and s["args"].get("in") in names


def per_save(ctx, keep: Callable[[dict], bool]) -> Optional[float]:
    """Seconds of the spans ``keep`` selects, per save of the window:
    saves are matched by step, and a save counts where the program
    recorded its ``ckpt.serialize`` span; None without one."""
    steps = {s["step"] for s in ctx["rec"]["saves"]}
    traced = {s["args"].get("step") for s in ctx["spans"]
              if s["name"] == "ckpt.serialize"} & steps
    if not traced:
        return None
    total = sum(s["dur"] for s in ctx["spans"]
                if keep(s) and s["args"].get("step") in traced)
    return total / len(traced)


def per_resume(ctx, keep: Callable[[dict], bool]) -> Optional[float]:
    """Seconds of the spans ``keep`` selects, per restore the program
    recorded with its ``ckpt.read`` span; None without a resume or one."""
    n = sum(1 for s in ctx["spans"] if s["name"] == "ckpt.read")
    if not ctx["rec"]["resumes"] or not n:
        return None
    return sum(s["dur"] for s in ctx["spans"] if keep(s)) / n


# ------------------------------------------------------ profiler trace


@dataclasses.dataclass
class ProgramSpans:
    own: List[Interval]      # on the thread lines of the benchmark's marks
    other: List[Interval]    # on the program's other thread lines


def load(path: str) -> ProgramSpans:
    """The program's spans in the host planes of a trace."""
    from jax.profiler import ProfileData
    own: List[Interval] = []
    other: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            marked = any(n.startswith(trace.HOST_PREFIX) for _, _, n in evs)
            (own if marked else other).extend(
                e for e in evs if e[2].startswith(PROGRAM_PREFIXES))
    own.sort()
    other.sort()
    return ProgramSpans(own, other)


def idle_gaps(tr: trace.Trace, own: List[Interval], k: int = 10):
    """``trace.idle_gaps`` with the spans of the marks' threads beside the
    marks: each gap is named after the innermost of either that covers its
    midpoint. The window stays the trace's."""
    return trace.idle_gaps(
        dataclasses.replace(tr, marks=sorted(tr.marks + own)), k)


def idle_inside(tr: trace.Trace, mark: str, during: List[Interval]
                ) -> float:
    """Seconds in which device 0 ran no op, inside the marks named
    ``mark`` and while an interval of ``during`` was open."""
    if not tr.ops:
        return 0.0
    dev = min(tr.ops)
    busy = trace.union(trace._clip(tr.ops[dev], tr.window))
    inside = trace.union([(s, e) for s, e, n in tr.marks if n == mark])
    open_ = trace.union(trace._clip(during, tr.window))
    total = 0
    for s, e in _intersect(inside, open_):
        total += (e - s) - sum(
            min(e, be) - max(s, bs) for bs, be in busy if be > s and bs < e)
    return total / 1e9


def _intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
