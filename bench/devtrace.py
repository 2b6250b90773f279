"""From a profiler trace to device times: the reduction every per-layer
metric that reads the trace goes through.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps three
lists of ``[name, start_ns, duration_ns]``:

* ``ops``: the chip's ``XLA Ops`` line, one event per executed HLO op or
  kernel, named by the op's name without its signature (``%fusion.157``;
  a Pallas kernel shows as its jitted function, ``%exit_head_entropy.1``).
  A loop's op (``%while.2``) spans the ops of its body, which follow it
  on the same line;
* ``modules``: the chip's ``XLA Modules`` line, one event per executed
  program, named after the jitted function (``jit_chunk(...)``);
* ``host``: the benchmark's own host spans (``bench.poll``,
  ``bench.wait``), on the same clock.

The traced window runs from the first host span's start to the last one's
end.  Device busy time is the union of op intervals inside it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Tuple

Event = List  # [name, start_ns, duration_ns]


def load(trace_dir: str, device: int = 0) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {paths}")
    pd = ProfileData.from_file(paths[0])
    out: Dict[str, List[Event]] = {"ops": [], "modules": [], "host": []}
    want = f"/device:TPU:{device}"
    for plane in pd.planes:
        if plane.name == want:
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] += [[e.name.split(" = ", 1)[0], e.start_ns,
                                  e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith("bench.")]
    return out


def read(path: str) -> Dict:
    """A reduced trace kept as gzipped JSON (the tests' recorded one)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(tr: Dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the traced window."""
    h = tr["host"]
    if not h:
        raise RuntimeError("the trace holds no bench.* host span")
    return min(e[1] for e in h), max(e[1] + e[2] for e in h)


def _clip(events: Iterable[Event], w: Tuple[float, float]):
    for _, s, d in events:
        a, b = max(s, w[0]), min(s + d, w[1])
        if b > a:
            yield a, b


def union_ns(events: Iterable[Event], w: Tuple[float, float]) -> float:
    """Length of the union of the events' intervals inside w."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(_clip(events, w)):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def busy_s(tr: Dict) -> float:
    return union_ns(tr["ops"], window(tr)) * 1e-9


def matching(events: Iterable[Event], patterns: Iterable[str],
             w: Tuple[float, float]) -> List[Event]:
    """Events starting inside w whose name matches one of the regexes."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in events
            if w[0] <= e[1] < w[1] and any(r.search(e[0]) for r in rx)]


CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


def top_ops(tr: Dict, n: int = 10) -> List[List]:
    """The n op names with the most device time in the window, seconds.
    Loops are left out: their time is their body's ops'."""
    w = window(tr)
    tot: Dict[str, float] = {}
    for name, s, d in tr["ops"]:
        if w[0] <= s < w[1] and not CONTAINERS.match(name):
            tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(tr: Dict, n: int = 10) -> List[List]:
    """The n longest stretches with no op on the device, each named by the
    host span that covers most of it (what the host was doing)."""
    w = window(tr)
    ivs = sorted(_clip(tr["ops"], w))
    gaps, t = [], w[0]
    for a, b in ivs:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        cover: Dict[str, float] = {}
        for name, s, d in tr["host"]:
            c = min(b, s + d) - max(a, s)
            if c > 0:
                cover[name] = cover.get(name, 0.0) + c
        who = max(cover, key=cover.get) if cover else "host.other"
        out.append([who, (b - a) * 1e-9])
    return out
