"""The serving scheduler's own host spans beside the device trace, and the
idle time they account for.

``serving/scheduler.py`` writes a ``sched.<phase>`` span
(``jax.profiler.TraceAnnotation``) around each phase of a poll: ``poll``,
``admit``, ``prefill_chunk``, ``go_live``, ``step``, ``dispatch``,
``commit``, and ``sync.<what>`` around every blocking device-to-host read.
They land in the same ``.xplane.pb`` as the chip's ops, on the same clock.

``load`` is ``devtrace.load`` plus a ``spans`` list of ``[name, start_ns,
duration_ns]`` holding those spans; ``host`` keeps only the benchmark's
``bench.*`` spans, so the traced window and every reader of
``devtrace`` read as before.  ``idle_gaps`` names each stretch with no op
on the chip by the innermost span open over most of it (a ``sched.*``
span inside ``bench.poll``), where ``devtrace.idle_gaps`` can name it only
``bench.poll``; ``idle_by_span`` sums all idle time of the window so.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

import devtrace

PREFIX = "sched."


def load(trace_dir: str, device: int = 0) -> Dict:
    """``devtrace.load`` of the trace, with the program's spans added."""
    from jax.profiler import ProfileData
    tr = devtrace.load(trace_dir, device)
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    tr["spans"] = [[e.name, e.start_ns, e.duration_ns]
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(PREFIX)]
    return tr


def innermost(spans) -> List[Tuple[float, float, str]]:
    """The time the spans cover, cut into ``(start, end, name)`` pieces in
    order, each given to the innermost span open over it (the spans of one
    thread nest: the one open longest started last)."""
    pieces, stack, t = [], [], 0.0
    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            pieces.append((t, end, top))
            t = end
        if stack:
            pieces.append((t, s, stack[-1][1]))
        stack.append((s + d, name))
        t = s
    while stack:
        end, top = stack.pop()
        pieces.append((t, end, top))
        t = end
    return [p for p in pieces if p[1] > p[0]]


def _gaps(tr: Dict) -> List[Tuple[float, float]]:
    """Stretches of the window with no op on the device, longest first."""
    w = devtrace.window(tr)
    gaps, t = [], w[0]
    for a, b in sorted(devtrace._clip(tr["ops"], w)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps


def _cover(pieces, starts, a: float, b: float) -> Dict[str, float]:
    """ns of [a, b) that each span name holds as the innermost."""
    cover: Dict[str, float] = {}
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][0] < b:
        s, e, name = pieces[i]
        c = min(b, e) - max(a, s)
        if c > 0:
            cover[name] = cover.get(name, 0.0) + c
        i += 1
    return cover


def idle_gaps(tr: Dict, n: int = 10) -> List[List]:
    """The n longest idle stretches of the device, seconds, each named by
    the span that is innermost over most of it; ``host.other`` where no
    span is open."""
    pieces = innermost(tr["host"] + tr.get("spans", []))
    starts = [p[0] for p in pieces]
    out = []
    for a, b in _gaps(tr)[:n]:
        cover = _cover(pieces, starts, a, b)
        who = max(cover, key=cover.get) if cover else "host.other"
        out.append([who, (b - a) * 1e-9])
    return out


def idle_by_span(tr: Dict) -> Dict[str, float]:
    """Seconds of device idle in the window, by the innermost span open
    over each instant of it (``host.other`` where none is)."""
    pieces = innermost(tr["host"] + tr.get("spans", []))
    starts = [p[0] for p in pieces]
    tot: Dict[str, float] = {}
    for a, b in _gaps(tr):
        cover = _cover(pieces, starts, a, b)
        cover["host.other"] = (b - a) - sum(cover.values())
        for name, c in cover.items():
            if c > 0:
                tot[name] = tot.get(name, 0.0) + c * 1e-9
    return tot


def named(tr: Dict, name: str) -> List[List]:
    """The program's spans called ``name`` that start in the window."""
    w = devtrace.window(tr)
    return [e for e in tr.get("spans", [])
            if e[0] == name and w[0] <= e[1] < w[1]]


def inside(events, outer) -> List[List]:
    """The events that start inside the span ``outer``."""
    return [e for e in events if outer[1] <= e[1] < outer[1] + outer[2]]
