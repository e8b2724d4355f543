"""docs/METRICS.md renderer: the instrument catalog, straight from code.

``telemetry.CATALOG`` is the single source of truth — binding an
undeclared instrument name raises at runtime, and this renderer keeps the
committed doc byte-identical to the declaration. ``scripts/ci.sh --lint``
drift-checks it with ``--check-metrics``, exactly like
``docs/PROTOCOL.md`` and ``--check-protocol``.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Tuple


def _catalog() -> Tuple[Tuple[str, str, str, str, str], ...]:
    try:
        from repro.core import telemetry
    except ImportError:
        src = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "src")
        sys.path.insert(0, os.path.abspath(src))
        from repro.core import telemetry
    return telemetry.CATALOG


def render() -> str:
    catalog = _catalog()
    by_owner: Dict[str, List[tuple]] = {}
    for name, kind, unit, owner, desc in catalog:
        by_owner.setdefault(owner, []).append((name, kind, unit, desc))
    lines = [
        "# Metrics registry",
        "",
        "<!-- GENERATED by `python -m tools.bbcheck --emit-metrics "
        "docs/METRICS.md` — do not edit by hand. -->",
        "",
        f"{len(catalog)} instruments declared in `telemetry.CATALOG` "
        "(`src/repro/core/telemetry.py`). Binding a name not listed here "
        "raises at construction, and `scripts/ci.sh --lint` fails when "
        "this file drifts from the declaration — so the doc, the code and "
        "the `metrics_query` / `BurstBufferSystem.scrape()` payloads can "
        "never disagree.",
        "",
        "Types: **counter** (monotonic per-label), **gauge** (last write "
        "wins), **histogram** (fixed half-decade latency buckets, "
        "10µs–10s + overflow), **ring** (bounded time series), **poll** "
        "(owner-supplied snapshot callback, read at scrape time), **span** "
        "(a trace span recorded after the fact, never bound).",
        "",
    ]
    for owner in sorted(by_owner):
        lines.append(f"## {owner}")
        lines.append("")
        lines.append("| name | type | unit | description |")
        lines.append("|---|---|---|---|")
        for name, kind, unit, desc in by_owner[owner]:
            lines.append(f"| `{name}` | {kind} | {unit} | {desc} |")
        lines.append("")
    return "\n".join(lines)
