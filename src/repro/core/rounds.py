"""Round-program engine: ONE schedule API for the sync WPFed round,
gossip epochs, and all baselines (DESIGN.md §8).

A federation method is a `RoundProgram` — two typed round bodies over
`FedState` (or any state pytree with `.round`):

  global_round(state, data) -> (state, cache, metrics)
      the full (expensive) composition — for WPFed: §3.6 reveal
      verification + LSH re-code + fused top-N re-selection, the
      all-in-one exchange, local updates, and the next announcement.
      `cache` is the program's selection cache (for WPFed the
      `SelectResult`; peer ids for the gossip baselines), threaded
      into the gossip epochs that follow.
  gossip_round(state, data, cache) -> (state, cache, metrics)
      a cheap epoch that REUSES the cached selection: exchange +
      update only — no re-code, no ranking/commitment announcement.
      This is the ProxyFL-style peer epoch (Kalra et al. 23) / P4
      peer-to-peer round (Maheri et al. 24) between global
      re-selections.

`Schedule(reselect_every=G)` partitions the round axis into
reselection periods: one global round followed by G-1 gossip epochs.
`make_segment_fn` compiles a whole period into ONE XLA program (the
gossip epochs run under `jax.lax.scan`), and `run_rounds` drives
segments with host sync only once per reselection — the `on_reselect`
callback is where `core.chain.Blockchain` publishing lives
(launch/fed.py, examples/wpfed_federation.py). This replaces the
per-round Python loops that previously forked per method.

`Schedule(reselect_every=1)` reproduces the classic sync protocol
bit-exactly for WPFed and every baseline (regression-tested in
tests/test_rounds_engine.py).

This module deliberately imports no `repro.core` siblings at module
level: `core.protocol` / `core.baselines` import `RoundProgram` from
here, and `make_program` resolves them via function-level imports
(the `repro.core.backends` pattern).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro import spans
from repro.analysis.privacy import declassifier, sink

# counted in the period program's Python body, so only when it traces
TRACES = "period.traces"


class RoundProgram(NamedTuple):
    """A federation method as a (global round, gossip epoch) pair."""
    name: str
    global_round: Callable  # (state, data) -> (state, cache, metrics)
    gossip_round: Optional[Callable] = None  # (state, data, cache) -> same


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Reselection schedule: run the global round every
    `reselect_every` rounds, gossip epochs in between. 1 == the
    paper's fully synchronous protocol."""
    reselect_every: int = 1

    def __post_init__(self):
        if self.reselect_every < 1:
            raise ValueError(
                f"reselect_every must be >= 1, got {self.reselect_every}")

    def segments(self, rounds: int):
        """Yield (start_round, length) per reselection period."""
        r0 = 0
        while r0 < rounds:
            yield r0, min(self.reselect_every, rounds - r0)
            r0 += self.reselect_every


SCHEDULES = ("sync", "gossip")


def resolve_schedule(name: str = "sync", reselect_every: int = 0) -> Schedule:
    """One-place schedule validation (the repro.core.backends pattern —
    launch/fed.py, examples and benchmarks all construct schedules
    here, so the string/argument checking lives in exactly one spot).

      "sync"   -> Schedule(1), the per-round protocol; an explicit
                  reselect_every other than 0/1 is an error, not
                  silently ignored.
      "gossip" -> Schedule(reselect_every or 4).
    """
    if name not in SCHEDULES:
        raise ValueError(
            f"unknown schedule: {name!r} (expected one of {SCHEDULES})")
    if name == "sync":
        if reselect_every not in (0, 1):
            raise ValueError(
                "schedule 'sync' re-selects every round; pass "
                "schedule='gossip' to use reselect_every="
                f"{reselect_every}")
        return Schedule(1)
    return Schedule(reselect_every or 4)


def program_round(program: RoundProgram) -> Callable:
    """Adapt a program's global round to the classic
    `round_fn(state, data) -> (state, metrics)` signature
    (make_wpfed_round and the make_*_round baselines are this adapter
    over their programs)."""

    def round_fn(state, data):
        state, _cache, metrics = program.global_round(state, data)
        return state, metrics

    return round_fn


@declassifier(
    name="round-telemetry", paper_eq="§4 (reported per-round metrics)",
    justification="federation-level scalar aggregates only (means and "
                  "fractions over the client axis) — the declassifier "
                  "refuses any non-scalar leaf, so no per-client vector "
                  "or model-derived array can ride this channel")
def release_round_telemetry(scalars: Dict[str, Any]) -> Dict[str, Any]:
    """The ONLY gate through which round metrics may reach the host tap.

    Raises on any non-scalar leaf: the justification above is enforced
    structurally, not by reviewer diligence."""
    for k, v in scalars.items():
        if getattr(v, "ndim", None) != 0:
            raise ValueError(
                f"round-telemetry releases scalars only; {k!r} has "
                f"shape {getattr(v, 'shape', None)!r}")
    return scalars


def _stream_metrics(metrics_tap: Callable, m: Dict[str, Any]) -> None:
    """Emit one round's scalar metrics to the host from INSIDE a
    compiled segment via an ordered `io_callback` (DESIGN.md §13): a
    continuous-service operator sees rounds as they complete instead of
    once per reselection period. Ordered so taps arrive in round order;
    non-scalar metrics (neighbor_ids, masks) stay on device."""
    scalars = {k: jnp.asarray(v) for k, v in m.items()}
    scalars = {k: v for k, v in scalars.items() if v.ndim == 0}
    # declassify (scalar aggregates, enforced above) THEN mark the
    # disclosure: the io_callback below carries only released values
    scalars = sink("metrics-tap", release_round_telemetry(scalars))

    def tap(s):  # analysis: host-ok — io_callback target runs on host
        metrics_tap({k: v.item() for k, v in s.items()})

    io_callback(tap, None, scalars, ordered=True)


def make_segment_fn(program: RoundProgram, length: int, *,
                    eval_fn: Optional[Callable] = None,
                    metrics_tap: Optional[Callable] = None) -> Callable:
    """Compile-ready body for one reselection period of `length`
    rounds: the global round, then length-1 gossip epochs under
    `jax.lax.scan` threading (state, cache). Returns
    segment_fn(state, data) -> (state, metrics) with every metric
    stacked on a leading (length,) round axis.

    `eval_fn(state, data) -> dict` (jittable, run under the named scope
    `evaluate`) is merged into each round's metrics — this keeps per-round evaluation inside the
    compiled segment instead of forcing a host sync per round.

    `metrics_tap(scalars: dict) -> None` (host function) additionally
    receives each round's scalar metrics mid-segment through an
    ordered `io_callback` — the service driver's live progress stream
    (`_stream_metrics`). Omitting it keeps the segment callback-free.
    """
    if length < 1:
        raise ValueError(f"segment length must be >= 1, got {length}")
    if length > 1 and program.gossip_round is None:
        raise ValueError(
            f"program {program.name!r} has no gossip_round; "
            "only Schedule(reselect_every=1) can run it")

    def scoped_eval(state, data):
        with jax.named_scope("evaluate"):
            return eval_fn(state, data)

    def seg_fn(state, data):
        spans.count(TRACES)
        state, cache, m0 = program.global_round(state, data)
        if eval_fn is not None:
            m0 = {**m0, **scoped_eval(state, data)}
        if metrics_tap is not None:
            _stream_metrics(metrics_tap, m0)
        if length == 1:
            # no scan: the segment IS the classic sync round
            # (bit-exactness with the pre-engine round is regression-
            # tested; keep this path free of extra graph structure)
            return state, jax.tree.map(lambda a: jnp.asarray(a)[None], m0)

        def body(carry, _):
            st, ca = carry
            st, ca, m = program.gossip_round(st, data, ca)
            if eval_fn is not None:
                m = {**m, **scoped_eval(st, data)}
            if metrics_tap is not None:
                _stream_metrics(metrics_tap, m)
            return (st, ca), m

        (state, _cache), ms = jax.lax.scan(
            body, (state, cache), None, length=length - 1)
        metrics = jax.tree.map(
            lambda a, b: jnp.concatenate([jnp.asarray(a)[None], b], axis=0),
            m0, ms)
        return state, metrics

    return seg_fn


def extract_history(metrics, r0, length):  # analysis: host-ok (see below)
    """Stacked per-round segment metrics -> one plain-Python dict per
    round (scalar metrics only, plus the absolute "round" index).
    Intentional host extraction: callers run it once per reselection
    period, after `jax.block_until_ready` (run_rounds here, the
    continuous service driver in `repro.service.driver`).

    The per-round scalars (the 1-D leaves) reach the host in one
    batched `jax.device_get`, one `host_pulls` per array; the other
    metrics (neighbor ids, masks, per-client vectors) stay on the
    device. Integer dtypes become `int`, every other dtype `float`."""
    stacked = {k: v for k, v in metrics.items()
               if getattr(v, "ndim", None) == 1}  # per-round scalars
    pulled = jax.device_get(stacked)
    spans.count(spans.HOST_PULLS, len(pulled))
    cols = []
    for k in stacked:  # the metrics' own order (device_get sorts keys)
        v = np.asarray(pulled[k])
        cast = int if np.issubdtype(v.dtype, np.integer) else float
        cols.append((k, cast, v))
    history: List[Dict[str, Any]] = []
    for i in range(length):
        entry: Dict[str, Any] = {k: cast(v[i]) for k, cast, v in cols}
        entry["round"] = r0 + i
        history.append(entry)
    return history


def run_period_program(seg_fn, state, data):
    """One call of a jitted period program, timed by the host period
    loops: `period.dispatch` up to the call's return (recorded as
    `period.compile` when the call traced the program, which is then
    registered with `spans` for `op_scopes`) and `period.wait` until
    its metrics are on hand. Returns (state, metrics, seconds of both)."""
    with spans.span("period.dispatch") as call:
        out_state, metrics = seg_fn(state, data)
        if call.counts.get(TRACES):
            call.name = "period.compile"
            spans.register_program(seg_fn, state, data)
    with spans.span("period.wait") as wait:
        jax.block_until_ready(metrics)
    return out_state, metrics, call.seconds + wait.seconds


def run_rounds(program: RoundProgram, state, data, *, rounds: int,
               schedule: Optional[Schedule] = None,
               eval_fn: Optional[Callable] = None,
               on_reselect: Optional[Callable] = None,
               log: Optional[Callable] = None
               ) -> Tuple[Any, List[Dict[str, Any]]]:
    """Drive `rounds` federation rounds under `schedule`.

    One jit-compiled segment per reselection period (compiled once per
    distinct length — at most two: full periods + a shorter tail);
    `on_reselect(start_round, state)` runs on host after each period
    with the period's announcements in `state` (codes / rankings /
    commitments are frozen across its gossip epochs), which is where
    the host `Blockchain` ledger publishes.

    Returns (final_state, history): one dict per round holding every
    scalar metric (plus `eval_fn` outputs) as a Python number and the
    absolute "round" index.

    Each period is recorded (`repro.spans`) as a `period` span holding
    `period.dispatch` or `period.compile`, `period.wait`,
    `period.on_reselect`, `period.history` and `period.log`.
    """
    schedule = schedule or Schedule()
    seg_fns: Dict[int, Callable] = {}
    history: List[Dict[str, Any]] = []
    for period, (r0, length) in enumerate(schedule.segments(rounds)):
        with spans.span("period", period=period):
            if length not in seg_fns:
                seg_fns[length] = jax.jit(
                    make_segment_fn(program, length, eval_fn=eval_fn))
            state, metrics, dt = run_period_program(seg_fns[length],
                                                    state, data)
            if on_reselect is not None:
                with spans.span("period.on_reselect"):
                    on_reselect(r0, state)
            with spans.span("period.history"):
                history.extend(extract_history(metrics, r0, length))
            if log is not None:
                with spans.span("period.log"):
                    last = history[-1]
                    parts = [f"{k} {last[k]:.4f}"
                             for k in ("acc", "mean_loss") if k in last]
                    log(f"round {last['round']:3d} " + " ".join(parts)
                        + f" ({dt:.1f}s/{length}r)")
    return state, history


PROGRAMS = ("wpfed", "silo", "fedmd", "proxyfl", "kdpdfl")


def make_program(method: str, apply_fn, optimizer, fed,
                 **kwargs) -> RoundProgram:
    """One-place program construction for every method name
    (`benchmarks.common` and the launchers resolve through here).
    `fedmd` requires shared_ref_x=...; `proxyfl` accepts num_peers=."""
    # function-level imports: protocol/baselines import RoundProgram
    # from this module (see the module docstring)
    from repro.core import baselines, protocol
    makers = {"wpfed": protocol.wpfed_program,
              **baselines.BASELINE_PROGRAMS}
    if method not in makers:
        raise KeyError(
            f"unknown method: {method!r} (expected one of {PROGRAMS})")
    return makers[method](apply_fn, optimizer, fed, **kwargs)
