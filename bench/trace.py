"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes are
named ``/device:TPU:<n>``; their op line holds one event per operation run on
the chip. Busy time is the union of those intervals; a kernel's time is the
sum of its events' durations. Host planes hold the benchmark's own
``TraceAnnotation`` spans (``bench.step``, ``bench.save``, ...), which name
what the host was doing during each gap in which the device was idle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a device plane that holds one event per operation
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."

Interval = Tuple[int, int, str]          # start ns, end ns, name


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]       # device id -> op events, by start
    marks: List[Interval]                # the benchmark's host spans
    window: Tuple[int, int]              # ns, the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str, window: Optional[Tuple[int, int]] = None) -> Trace:
    """Read a trace. ``window`` (ns on the trace's clock) defaults to the
    span of the benchmark's host marks, else of every device op."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    marks: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in OP_LINES:
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events)
            elif not m:
                marks.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(HOST_PREFIX))
    for evs in ops.values():
        evs.sort()
    marks.sort()
    return from_events(ops, marks, window)


def from_events(ops: Dict[int, List[Interval]], marks: List[Interval],
                window: Optional[Tuple[int, int]] = None) -> Trace:
    if window is None:
        spans = marks or [e for evs in ops.values() for e in evs]
        if not spans:
            raise ValueError("the trace holds no device op and no mark")
        window = (min(s for s, _, _ in spans), max(e for _, e, _ in spans))
    return Trace(ops=ops, marks=marks, window=window)


def _clip(evs: Sequence[Interval], window) -> List[Tuple[int, int]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e, _ in evs if e > lo and s < hi]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an op ran, averaged over the chips
    that ran any."""
    if not trace.ops:
        return 0.0
    per = [sum(e - s for s, e in union(_clip(evs, trace.window)))
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, in percent; None without a device op."""
    busy = busy_s(trace)
    if busy <= 0 or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def kernel_time(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(seconds, events) of the ops whose name matches ``pattern``, summed
    over the window and the chips."""
    rx = re.compile(pattern)
    total, n = 0, 0
    for evs in trace.ops.values():
        for s, e, name in evs:
            if rx.search(name) and e > trace.window[0] and s < trace.window[1]:
                total += min(e, trace.window[1]) - max(s, trace.window[0])
                n += 1
    return total / 1e9, n


# ops whose events enclose the ops they run, so that their time is counted
# again by what they hold
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """'%fusion.3 = bf16[..] fusion(...), ...' -> 'fusion.3 fusion': the op's
    name and kind, with a custom call's target."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:120]
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
    m = re.search(r"\s([a-z][\w-]*)\(", rhs[i:])
    kind = m.group(1) if m else "?"
    t = re.search(r'custom_call_target="([^"]+)"', rhs)
    return " ".join([lhs.lstrip("%"), kind] + ([t.group(1)] if t else []))


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` ops that took most device time, [short name, seconds];
    ops that only enclose others are left out."""
    agg: Dict[str, int] = {}
    for evs in trace.ops.values():
        for s, e, name in evs:
            short = short_name(name)
            if short.split(" ")[1:2] and short.split(" ")[1] in CONTAINERS:
                continue
            agg[short] = agg.get(short, 0) + (e - s)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` longest gaps in which no op ran on device 0's timeline (the
    first chip), each named by the innermost benchmark mark that covers
    its midpoint, [name, seconds]."""
    if not trace.ops:
        return []
    dev = min(trace.ops)
    busy = union(_clip(trace.ops[dev], trace.window))
    edges = [trace.window[0]] + [x for iv in busy for x in iv] \
        + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) // 2
        cover = [m for m in trace.marks if m[0] <= mid < m[1]]
        name = min(cover, key=lambda m: m[1] - m[0])[2] if cover \
            else "host: outside the benchmark's marks"
        out.append([name, (e - s) / 1e9])
    return out
