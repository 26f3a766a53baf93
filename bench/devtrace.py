"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device-busy time, idle gaps and what the host was doing in them,
per-period device time and per-kernel time.

`load` reads the `.xplane.pb` that `jax.profiler` wrote into plain
event lists (name, start ns, end ns); everything else works on those
lists, so a test can hand it a small excerpt (`read`).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

WINDOW = "bench.window"               # host span around the traced periods
SEGMENT = "seg_fn"                    # the period program's XLA module


def load(trace_dir: str) -> Dict[str, List[Event]]:
    """{"ops": device op events of the first TPU, "modules": its XLA
    module events, "host": host-thread spans}, each sorted by start."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"ops": [], "modules": [], "host": []}
    data = ProfileData.from_file(sorted(files)[-1])
    out = {"ops": [], "modules": [], "host": []}
    devices = sorted((p for p in data.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    for plane in devices[:1]:
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:
                out[key] += [(op_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events]
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events
                                if e.name.startswith("bench.")]
    for v in out.values():
        v.sort(key=lambda e: e[1])
    return out


def op_name(text: str) -> str:
    """An op event's HLO instruction name: the trace names each op by
    its whole instruction text ("%fusion.426 = f32[...] fusion(...)")."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> Dict[str, List[Event]]:
    with open(path) as f:
        return {k: [tuple(e) for e in v] for k, v in json.load(f).items()}


def window(events) -> Optional[Tuple[int, int]]:
    spans = [e for e in events["host"] if e[0] == WINDOW]
    return (spans[0][1], spans[0][2]) if spans else None


def union(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_ns(events, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(
        ((s, e) for _, s, e in events["ops"]), lo, hi))


def idle_gaps(events, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals of [lo, hi) in which no device op runs."""
    gaps, t = [], lo
    for s, e in union(((s, e) for _, s, e in events["ops"]), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def segments(events, lo: int, hi: int) -> List[Event]:
    """Executions of the period program inside [lo, hi)."""
    return [m for m in events["modules"]
            if SEGMENT in m[0] and m[1] >= lo and m[2] <= hi]


def host_gaps_ns(events, lo: int, hi: int) -> List[int]:
    """Device-idle time between the end of one period program and the
    start of the next, one entry per consecutive pair."""
    segs = segments(events, lo, hi)
    return [sum(e - s for s, e in idle_gaps(events, a[2], b[1]))
            for a, b in zip(segs, segs[1:])]


def segment_busy_ns(events, lo: int, hi: int) -> List[int]:
    """Device-busy time inside each period program's execution."""
    return [busy_ns(events, s, e) for _, s, e in segments(events, lo, hi)]


def kernel_ns(events, prefix: str, lo: int, hi: int) -> Tuple[int, int]:
    """(summed device time, count) of op events whose name starts with
    `prefix` (a kernel's custom call is named after its jitted entry),
    inside [lo, hi)."""
    hits = [(s, e) for n, s, e in events["ops"]
            if n.startswith(prefix) and s >= lo and e <= hi]
    return sum(e - s for s, e in hits), len(hits)


def attribute(events, gap: Tuple[int, int]) -> str:
    """What the host was doing in an idle gap: the harness span that
    covers its midpoint, else the last one that ended before it."""
    mid = (gap[0] + gap[1]) // 2
    spans = [h for h in events["host"] if h[0] != WINDOW]
    for name, s, e in spans:
        if s <= mid < e:
            return name
    before = [h for h in spans if h[2] <= mid]
    return ("after " + max(before, key=lambda h: h[2])[0]) if before \
        else "before any host span"


CONTAINERS = ("while", "conditional", "call")


def breakdown(events, lo: int, hi: int, top: int = 10) -> dict:
    """The ops that took most device time (loops, whose bodies' ops are
    counted on their own, left out) and the longest idle gaps with what
    the host was doing."""
    per_op: Dict[str, int] = {}
    for n, s, e in events["ops"]:
        if s >= lo and e <= hi and not n.startswith(CONTAINERS):
            per_op[n] = per_op.get(n, 0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[attribute(events, g), (g[1] - g[0]) / 1e9]
                          for g in gaps[:top]]}
