"""The span and counter recorder (`repro.spans`) and what the host
period loops record with it.

  * spans nest by thread, are recorded when they exit (by an exception
    too), the ring keeps the last RING, totals outlive it, and counts
    attach to the innermost open span;
  * `run_rounds` records one `period` span per period with its
    children in order, `period.compile` once per segment length, and
    `host_pulls` = the history's arrays (one per metric, whatever the
    period's length) plus the publisher's two;
  * `run_service` records `period.checkpoint` every `checkpoint_every`
    and counts one pull per history array, per transported array and
    per checkpointed leaf;
  * under a CPU profiler trace every recorded span is on the host
    timeline under its name, with the recorder's duration;
  * `op_scopes()` maps the period program's ops to all five phases
    without compiling anything.
"""
import dataclasses
import glob
import os
import time

import jax
import pytest

from repro import spans
from repro.core import Schedule, evaluate, init_state, run_rounds
from repro.core import wpfed_program
from repro.core.chain import Blockchain
from repro.core.protocol import EXCHANGE_CHUNK, UPDATE_CHUNK
from repro.launch.fed import chain_publisher
from repro.service import ServiceConfig, init_service_state, run_service

M = 4


def _new_spans(before):
    """Spans recorded since snapshot `before`, oldest first."""
    last = max((s["id"] for s in before["spans"]), default=-1)
    return [s for s in spans.snapshot()["spans"] if s["id"] > last]


def _counter(name):
    return spans.snapshot()["counters"].get(name, 0)


def _children(recorded, parent):
    return [s for s in recorded if s["parent"] == parent["id"]]


def _under(recorded, parent):
    """Every span below `parent`."""
    out = _children(recorded, parent)
    for s in list(out):
        out += _under(recorded, s)
    return out


@pytest.fixture(scope="module")
def fed4(tiny_fed):
    f = dict(tiny_fed)
    f["fed"] = dataclasses.replace(f["fed"], num_clients=M)
    f["data"] = {k: v[:M] for k, v in f["data"].items()}
    f["state0"] = init_state(f["apply_fn"], f["init_fn"], f["opt"],
                             f["fed"], jax.random.PRNGKey(0))
    return f


def _eval(f):
    return lambda st, d: {"acc": evaluate(f["apply_fn"], st, d)["mean_acc"]}


def _run_rounds(f, rounds, schedule=None, log=None, publish=True):
    program = wpfed_program(f["apply_fn"], f["opt"], f["fed"])
    on_reselect = chain_publisher(Blockchain(), M) if publish else None
    return run_rounds(program, f["state0"], f["data"], rounds=rounds,
                      schedule=schedule, eval_fn=_eval(f),
                      on_reselect=on_reselect, log=log)


# ---------------------------------------------------------------- recorder
def test_nesting_parents_and_counts():
    before = spans.snapshot()
    total = _counter("test.things")
    with spans.span("test.outer", period=7) as outer:
        spans.count("test.things")
        with spans.span("test.inner") as inner:
            spans.count("test.things", 3)
        with pytest.raises(ValueError):
            with spans.span("test.raises"):
                raise ValueError("recorded all the same")
    got = {s["name"]: s for s in _new_spans(before)}
    assert [s["name"] for s in _new_spans(before)] == [
        "test.inner", "test.raises", "test.outer"]
    assert got["test.outer"]["parent"] is None
    assert got["test.inner"]["parent"] == outer.id
    assert got["test.raises"]["parent"] == outer.id
    assert got["test.outer"]["args"] == {"period": 7}
    assert got["test.outer"]["counts"] == {"test.things": 1}
    assert got["test.inner"]["counts"] == {"test.things": 3}
    assert _counter("test.things") == total + 4
    s_in = got["test.inner"]
    assert s_in["end_ns"] - s_in["start_ns"] == inner.end_ns - inner.start_ns
    assert outer.start_ns <= s_in["start_ns"] <= s_in["end_ns"] \
        <= outer.end_ns


def test_ring_keeps_the_last_spans_and_totals_outlive_it():
    before = spans.snapshot()["totals"].get("test.ring", {"count": 0})
    for i in range(spans.RING + 10):
        with spans.span("test.ring", i=i):
            pass
    snap = spans.snapshot()
    assert len(snap["spans"]) == spans.RING
    assert [s["args"]["i"] for s in snap["spans"][:2]] == [10, 11]
    assert snap["spans"][-1]["args"]["i"] == spans.RING + 9
    assert snap["totals"]["test.ring"]["count"] == \
        before["count"] + spans.RING + 10


# -------------------------------------------------------------- run_rounds
def test_run_rounds_records_each_period_and_its_children(fed4):
    before = spans.snapshot()
    pulls = _counter(spans.HOST_PULLS)
    _, history = _run_rounds(fed4, rounds=3, log=lambda line: None)
    recorded = _new_spans(before)
    periods = [s for s in recorded if s["name"] == "period"]
    assert [p["args"] for p in periods] == [{"period": k} for k in range(3)]
    for k, p in enumerate(periods):
        first = "period.compile" if k == 0 else "period.dispatch"
        assert [c["name"] for c in _children(recorded, p)] == [
            first, "period.wait", "period.on_reselect", "period.history",
            "period.log"]
    # every span of a period lies under its `period` span
    assert all(s["name"] == "period" or s["parent"] is not None
               for s in recorded)
    publish = [s for s in recorded if s["name"] == "ledger.publish"]
    assert len(publish) == 3 and all(
        s["counts"] == {spans.HOST_PULLS: 2} for s in publish)
    # one array of length 1 per metric: 7 round metrics and the accuracy
    arrays = 8
    assert all(len(entry) == arrays for entry in history)
    assert _counter(spans.HOST_PULLS) - pulls == 3 * (arrays + 2)
    per_period = [sum(s["counts"].get(spans.HOST_PULLS, 0)
                      for s in _under(recorded, p)) for p in periods]
    assert per_period == [arrays + 2] * 3


def test_compile_is_recorded_once_per_segment_length(fed4):
    before = spans.snapshot()
    traces = _counter("period.traces")
    # periods of 3, 3 and a tail of 1 round
    _run_rounds(fed4, rounds=7, schedule=Schedule(3), publish=False)
    recorded = _new_spans(before)
    calls = [s["name"] for s in recorded
             if s["name"] in ("period.compile", "period.dispatch")]
    assert calls == ["period.compile", "period.dispatch", "period.compile"]
    assert _counter("period.traces") - traces == 2
    # each trace also counts the local update's and the personal
    # exchange's chunk widths (1 on the CPU) once per phase it traces:
    # the global round and the gossip epoch's scan body, then the tail
    # period's global round alone
    assert [s["counts"] for s in recorded
            if s["name"] == "period.compile"] == [
        {"period.traces": 1, UPDATE_CHUNK: 2, EXCHANGE_CHUNK: 2},
        {"period.traces": 1, UPDATE_CHUNK: 1, EXCHANGE_CHUNK: 1}]
    # the history pulls 8 arrays a period, of 3, 3 and 1 rounds
    assert [s["counts"] for s in recorded
            if s["name"] == "period.history"] == [{spans.HOST_PULLS: 8}] * 3


def test_log_time_is_the_dispatch_and_wait_spans(fed4):
    lines = []
    before = spans.snapshot()
    _run_rounds(fed4, rounds=2, log=lines.append, publish=False)
    recorded = _new_spans(before)
    for line, p in zip(lines, [s for s in recorded if s["name"] == "period"]):
        kids = _children(recorded, p)
        dt = sum(s["end_ns"] - s["start_ns"] for s in kids[:2]) / 1e9
        assert line.endswith(f"({dt:.1f}s/1r)")


# ------------------------------------------------------------- run_service
def test_run_service_checkpoints_every_checkpoint_every(fed4, tmp_path):
    svc = ServiceConfig(reselect_every=2, checkpoint_every=2, keep_last_k=2)
    state = init_service_state(fed4["state0"], svc)
    before = spans.snapshot()
    run_service(fed4["apply_fn"], fed4["opt"], fed4["fed"], svc, state,
                fed4["data"], periods=5, ckpt_dir=str(tmp_path))
    recorded = _new_spans(before)
    periods = {s["id"]: s["args"]["period"] for s in recorded
               if s["name"] == "period"}
    assert sorted(periods.values()) == list(range(5))
    ckpt = [periods[s["parent"]] for s in recorded
            if s["name"] == "period.checkpoint"]
    assert ckpt == [1, 3]
    names = [c["name"] for c in recorded
             if c["parent"] == min(periods)]
    assert names == ["period.events", "period.compile", "period.wait",
                     "ledger.collect", "ledger.publish", "ledger.fetch",
                     "period.history"]
    collect = [s for s in recorded if s["name"] == "ledger.collect"]
    assert all(s["counts"] == {spans.HOST_PULLS: 3} for s in collect)
    # one pull per history array (the service's 10 round metrics, each
    # of length 2), per transported array and per checkpointed leaf
    history = [s for s in recorded if s["name"] == "period.history"]
    assert [s["counts"] for s in history] == [{spans.HOST_PULLS: 10}] * 5
    leaves = len(jax.tree.leaves(state))
    per_period = [sum(s["counts"].get(spans.HOST_PULLS, 0)
                      for s in _under(recorded, p))
                  for p in recorded if p["name"] == "period"]
    assert per_period == [10 + 3, 10 + 3 + leaves] * 2 + [10 + 3]


# ------------------------------------------------------------ shared clock
def test_spans_are_on_the_profiler_timeline(fed4, tmp_path):
    from jax.profiler import ProfileData
    window = {}

    def log(line):
        # trace from the end of the first period to the fourth's log
        if "start" not in window:
            jax.profiler.start_trace(str(tmp_path))
            window["start"] = time.perf_counter_ns()
        elif line.startswith("round   3"):
            window["stop"] = time.perf_counter_ns()
            jax.profiler.stop_trace()

    before = spans.snapshot()
    _run_rounds(fed4, rounds=4, log=log)
    recorded = [s for s in _new_spans(before)
                if s["start_ns"] > window["start"]
                and s["end_ns"] < window["stop"]]
    names = {s["name"] for s in recorded}
    assert {"period", "period.dispatch", "period.wait", "period.history",
            "ledger.publish"} <= names
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    timeline = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        timeline.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns))
    for name in names:
        mine = [s["end_ns"] - s["start_ns"] for s in recorded
                if s["name"] == name]
        theirs = [d for _, d in sorted(timeline.get(name, []))]
        assert len(theirs) == len(mine), name
        for a, b in zip(mine, theirs):
            assert abs(a - b) <= max(0.1 * a, 0.2e6), (name, a, b)


# ------------------------------------------------------------ phase scopes
def test_op_scopes_covers_the_five_phases_without_compiling(fed4):
    _run_rounds(fed4, rounds=1, publish=False)
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: events.append(event))
    scopes = spans.op_scopes()
    assert set(scopes.values()) == set(spans.PHASES)
    assert not [e for e in events if "backend_compile" in e]
    assert spans.op_scopes() is scopes      # built once


def test_scopes_of_hlo_roots_and_neighbours():
    text = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), '
        'metadata={op_name="jit(seg_fn)/update/while/body/mul"}',
        "}",
        "ENTRY %main.9 (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        '  %sine.2 = f32[4]{0} sine(%a), '
        'metadata={op_name="jit(seg_fn)/select/jvp(exchange)/sin"}',
        "  %copy.3 = f32[4]{0} copy(%sine.2)",
        "  %fusion.4 = f32[4]{0} fusion(%copy.3), kind=kLoop, "
        'calls=%fused_computation.1, metadata={op_name="jit(seg_fn)/x"}',
        "  %copy.5 = f32[4]{0} copy(%a)",
        '  ROOT %custom-call.6 = f32[4]{0} custom-call(%copy.5, %fusion.4), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(seg_fn)/evaluate/pallas_call"}',
        "}",
    ])
    scopes = spans.scopes_of_hlo(text)
    assert scopes["sine.2"] == "select"            # the outermost phase
    assert scopes["fusion.4"] == "update"          # its root's scope
    assert scopes["copy.3"] == "select"            # its producer's
    assert scopes["copy.5"] == "evaluate"          # no producer: its user's
    assert scopes["custom-call.6"] == "evaluate"

