"""Reduction of the program's own span record (`repro.spans`) to the
numbers the per-layer metrics read.

The metrics that read spans average over the last n `period` spans,
n being the traced periods (`len(ctx["periods_s"])`): the loop records
every period, and the traced ones are its last. Each function takes
the snapshot (`snapshot()`, plain data) or the scope map (`scopes()`)
as an argument, so a test can hand it a recorded fixture.

A program without the recorder gives `snapshot()` and `scopes()` None,
and every metric then reports nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import devtrace as tr

PERIOD = "period"
# the period loop's own work between one period program and the next
BETWEEN = ("period.dispatch", "period.compile", "period.history",
           "period.on_reselect", "period.events", "period.checkpoint",
           "period.log")
LEDGER = "ledger."


def _spans_module():
    try:
        from repro import spans
    except ImportError:
        return None
    return spans


def snapshot() -> Optional[dict]:
    spans = _spans_module()
    return spans.snapshot() if spans else None


def scopes() -> Optional[Dict[str, str]]:
    spans = _spans_module()
    return spans.op_scopes() if spans else None


def periods(snap: dict, n: int) -> List[List[dict]]:
    """The last n `period` spans, each as [the period span, then every
    span under it]."""
    by_parent: Dict[int, List[dict]] = {}
    for s in snap["spans"]:
        by_parent.setdefault(s["parent"], []).append(s)
    tops = [s for s in snap["spans"] if s["name"] == PERIOD][-n:] \
        if n > 0 else []
    out = []
    for top in tops:
        group, todo = [top], [top["id"]]
        while todo:
            kids = by_parent.get(todo.pop(), [])
            group += kids
            todo += [k["id"] for k in kids]
        out.append(group)
    return out


def _ns(s: dict) -> int:
    return s["end_ns"] - s["start_ns"]


def per_period_ms(snap: dict, n: int, keep,
                  self_time: bool = False) -> Optional[float]:
    """Mean over the last n periods of the summed time of the spans
    `keep(name)` picks (less their children's time with `self_time`),
    in ms."""
    groups = periods(snap, n)
    if not groups:
        return None
    total = 0
    for group in groups:
        for s in group[1:]:
            if not keep(s["name"]):
                continue
            total += _ns(s)
            if self_time:
                total -= sum(_ns(k) for k in group if k["parent"] == s["id"])
    return total / len(groups) / 1e6


def per_period_count(snap: dict, n: int, name: str) -> Optional[float]:
    """Mean over the last n periods of counter `name`, counted in the
    period span or any span under it."""
    groups = periods(snap, n)
    if not groups:
        return None
    return sum(s["counts"].get(name, 0) for g in groups for s in g) \
        / len(groups)


def n_periods(ctx) -> int:
    return len(ctx["periods_s"])


def phase_device_ms(events, scope_map: Dict[str, str], lo: int,
                    hi: int) -> Dict[Optional[str], float]:
    """{phase: device-busy ms per period program}. Each instant of a
    program's busy time counts to the innermost op running then: a
    loop instruction's own time, between its body's ops, to the loop's
    phase. Instants whose op has no phase count to None, so the values
    sum to `segment_device_ms`."""
    segs = tr.segments(events, lo, hi)
    if not segs or not scope_map:
        return {}
    ops = sorted(events["ops"], key=lambda op: (op[1], -op[2]))
    out: Dict[Optional[str], float] = {}

    def add(phase, ns):
        out[phase] = out.get(phase, 0.0) + ns / 1e6 / len(segs)

    for _, a, b in segs:
        t, stack = a, []                # stack: (end, phase), innermost last
        for name, s, e in ops:
            if e <= a or s >= b:
                continue
            s, e = max(s, a), min(e, b)
            while stack and stack[-1][0] <= s:
                end, phase = stack.pop()
                if end > t:
                    add(phase, end - t)
                    t = end
            if stack and s > t:
                add(stack[-1][1], s - t)
            t = max(t, s)
            stack.append((e, scope_map.get(name)))
        while stack:
            end, phase = stack.pop()
            if end > t:
                add(phase, end - t)
                t = end
    return out


def read_phase(ctx, phase: str) -> Optional[float]:
    scope_map = scopes()
    if not scope_map or not ctx.get("window"):
        return None
    per_phase = phase_device_ms(ctx["events"], scope_map, *ctx["window"])
    return per_phase.get(phase, 0.0) if per_phase else None


def between_ms(snap: dict, n: int) -> Optional[float]:
    """Host time per gap between two of the last n period programs that
    the loop's own spans account for: a gap holds the tail of one
    period (its children after `period.wait`) and the head of the next
    (its children before it), in ms. The last period's tail holds the
    end of the run, and no gap."""
    groups = periods(snap, n)
    if len(groups) < 2:
        return None
    total = 0
    for k, group in enumerate(groups):
        kids = sorted((s for s in group if s["parent"] == group[0]["id"]),
                      key=lambda s: s["start_ns"])
        waits = [s["start_ns"] for s in kids if s["name"] == "period.wait"]
        if not waits:
            return None
        for s in kids:
            if not (s["name"] in BETWEEN or s["name"].startswith(LEDGER)):
                continue
            tail = s["start_ns"] > waits[0]
            if (tail and k < len(groups) - 1) or (not tail and k > 0):
                total += _ns(s)
    return total / (len(groups) - 1) / 1e6


def untraced_gap_ms(events, snap: dict, n: int, lo: int,
                    hi: int) -> Optional[float]:
    """The mean device-idle time between period programs (what
    `host_gap_ms` reads) less the loop's spans in such a gap
    (`between_ms`), floored at 0, in ms."""
    gaps = tr.host_gaps_ns(events, lo, hi)
    spanned = between_ms(snap, n)
    if not gaps or spanned is None:
        return None
    return max(0.0, sum(gaps) / len(gaps) / 1e6 - spanned)
