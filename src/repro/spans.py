"""In-process spans and counters: the program's one timing facility.

`span(name, **args)` times a block of host code twice over. It opens a
`jax.profiler.TraceAnnotation`, so the block lands on the profiler's
host timeline, on the same clock as the device ops it launched, and it
records `(name, start_ns, end_ns, parent, args)` in memory by
`time.perf_counter_ns()`, with `parent` the span that was open around
it on the same thread. A span that exits by an exception is recorded
too. `count(name, n)` adds to the innermost open span's counts and to
a running total per name.

The record keeps the last `RING` spans, plus a running count and total
time per span name, so a long run's totals outlive the ring.
`snapshot()` returns all of it as plain Python data. Spans and
counters carry names, times, period indices and counts; never a
device value.

The period program's phases are named inside the program by
`jax.named_scope` (`PHASES`). The period loops register the jitted
program with the argument shapes it last compiled for
(`register_program`); `op_scopes()` maps that compiled program's HLO
instructions to phases, so device time in a profile can be read per
phase. It is built only when asked, after the run, from the executable
the loop already holds: it compiles nothing new.
"""
from __future__ import annotations

import collections
import itertools
import re
import threading
import time
from typing import Any, Dict, Optional

import jax

RING = 8192
# the counter of device-to-host transfers in the host period loop
HOST_PULLS = "host_pulls"
PHASES = ("select", "exchange", "update", "announce", "evaluate")

_local = threading.local()
_lock = threading.Lock()
_ring: collections.deque = collections.deque(maxlen=RING)
_totals: Dict[str, list] = {}           # name -> [count, ns]
_counters: Dict[str, int] = {}
_ids = itertools.count()
_program: Optional[tuple] = None        # (jitted fn, argument shapes)
_scopes: Optional[Dict[str, str]] = None


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One span; `span()` makes it. It is recorded under the `name` it
    has when it closes; the profiler's timeline keeps the name it
    opened with."""

    __slots__ = ("id", "name", "args", "parent", "start_ns", "end_ns",
                 "counts", "_annotation")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name, self.args, self.counts = name, args, {}

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.args)
        self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _stack().pop()
        with _lock:
            _ring.append((self.id, self.name, self.start_ns, self.end_ns,
                          self.parent, self.args, self.counts))
            total = _totals.setdefault(self.name, [0, 0])
            total[0] += 1
            total[1] += self.end_ns - self.start_ns
        return False


def span(name: str, **args) -> Span:
    """Context manager: time the block as `name` (see the module
    docstring). `args` (a period index, say) go with the record and
    onto the timeline."""
    return Span(name, args)


def count(name: str, n: int = 1) -> None:
    """Add `n` to the innermost open span's counts and to the total."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
    stack = _stack()
    if stack:
        counts = stack[-1].counts
        counts[name] = counts.get(name, 0) + n


def snapshot() -> Dict[str, Any]:
    """The record as plain Python data: the ring's spans, oldest
    first, the count and total time per span name, and every
    counter's total."""
    with _lock:
        spans = [{"id": i, "name": n, "start_ns": s, "end_ns": e,
                  "parent": p, "args": dict(a), "counts": dict(c)}
                 for i, n, s, e, p, a, c in _ring]
        totals = {n: {"count": c, "ns": ns}
                  for n, (c, ns) in _totals.items()}
        counters = dict(_counters)
    return {"spans": spans, "totals": totals, "counters": counters}


# ------------------------------------------------------------ phase scopes
def register_program(fn, *args) -> None:
    """Note `fn` (a jitted period program) and the shapes of the
    arguments it was just compiled for. Only shapes, dtypes and
    placements are kept, never the arrays."""
    global _program, _scopes

    def shape(a):
        aval = jax.typeof(a)
        return jax.ShapeDtypeStruct(
            aval.shape, aval.dtype, weak_type=aval.weak_type,
            sharding=a.sharding if getattr(a, "committed", False)
            else None)

    _program, _scopes = (fn, jax.tree.map(shape, args)), None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(")
_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
_CALLS = re.compile(r"calls=%([\w.-]+)")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_REF = re.compile(r"%([\w.-]+)")


def _phase(op_name: Optional[str]) -> Optional[str]:
    """The outermost name-stack component that is a phase."""
    for part in (op_name or "").split("/"):
        if part in PHASES:
            return part
    return None


def _nearest(start: str, links, phase: Dict[str, str]) -> Optional[str]:
    """The phase of the instruction nearest `start` along `links`."""
    seen, frontier = {start}, [start]
    while frontier:
        step = []
        for name in frontier:
            for nxt in links(name):
                if nxt in phase:
                    return phase[nxt]
                if nxt not in seen:
                    seen.add(nxt)
                    step.append(nxt)
        frontier = step
    return None


def scopes_of_hlo(text: str) -> Dict[str, str]:
    """{instruction name: phase} of an HLO module's text, in every
    computation (loop bodies included). A fusion takes its root's
    scope. An instruction the compiler added (a layout copy, a
    prefetch) has no scope of its own: it takes its nearest producer's,
    else its nearest user's."""
    roots: Dict[str, Optional[str]] = {}
    insts = {}                  # name -> (called computation, op_name)
    operands: Dict[str, list] = {}
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        name = _NAME.match(line)
        if not name:
            continue
        name = name.group(1)
        calls = _CALLS.search(line)
        meta = _OP_NAME.search(line)
        insts[name] = (calls and calls.group(1), meta and meta.group(1))
        operands[name] = _REF.findall(line, line.index(" = "))
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = insts[name][1]
    phase = {}
    for name, (calls, op_name) in insts.items():
        found = (_phase(roots.get(calls)) if calls else None) \
            or _phase(op_name)
        if found:
            phase[name] = found
    users: Dict[str, list] = {}
    for name, refs in operands.items():
        operands[name] = [r for r in refs if r in insts and r != name]
        for r in operands[name]:
            users.setdefault(r, []).append(name)
    out = dict(phase)
    for name in insts:
        if name not in phase:
            found = (_nearest(name, operands.__getitem__, phase)
                     or _nearest(name, lambda n: users.get(n, ()), phase))
            if found:
                out[name] = found
    return out


def op_scopes() -> Dict[str, str]:
    """{HLO instruction name: phase} of the period program registered
    last, or {} when none is. Built once per registration, from the
    executable the jitted program already holds for those shapes."""
    global _scopes
    if _program is None:
        return {}
    if _scopes is None:
        fn, args = _program
        _scopes = scopes_of_hlo(fn.lower(*args).compile().as_text())
    return _scopes
