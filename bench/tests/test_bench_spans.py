"""The readers of the program's span record and phase scopes
(`bench/progspans.py`, the metrics that use it) on a recorded fixture
(`fixtures/spans_excerpt.json`), against values worked out by hand
(times in ms):

  trace: period programs A 1.0-3.0 and B 6.0-8.0; a slice op 4.0-4.2
    between them. Each instant counts to the innermost op: the update
    loop `while.4` (2.0-2.8 in A) holds `fusion.5` (2.0-2.5) and the
    announce's kernel (2.6-2.8), so 2.5-2.6 is the loop's own, update:
      select    A 1.0-1.5 = 0.5   B 6.0-6.4 = 0.4  -> 0.45
      exchange  A 0.2 + 0.3 = 0.5 B 0.2 + 0.2 = 0.4 -> 0.45
      update    A 0.5 + 0.1 = 0.6 B 0.8            -> 0.7
      announce  A 0.2             B 0.2            -> 0.2
      evaluate  A 0.1             B 0.1            -> 0.1
    an unscoped add.8 (0.1 each): the phases hold 1.9 of each
    program's 2.0 busy ms. Host gap: 3.0-6.0 less the slice = 2.8.
  span record: periods 0, 1, 2; the traced are the last n = 2.
      period.history self  1: 0.8   2: 1.2 - 0.2 (history.part) = 1.0
                                                         -> 0.9
      ledger.*             1: 0.4   2: 0.3               -> 0.35
      period.checkpoint    1: 0     2: 1.0               -> 0.5
      host_pulls           1: 2 + 8 = 10   2: 2 + 8 + 1 = 11 -> 10.5
      between-segment spans in the one gap, period 1's after its
      wait and period 2's before it: 0.6 + 0.8 + 0.1 + 0.3 = 1.8
      (period 1's dispatch and period 2's tail border no gap)
      untraced gap 2.8 - 1.8 = 1.0
      compile_s: the one period.compile, 8 ms -> 0.008 s
"""
import json
import os

import pytest

from benchkit import BENCH

import progspans
import run

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "spans_excerpt.json")
NEW = ("select_device_ms", "exchange_device_ms", "update_device_ms",
       "announce_device_ms", "evaluate_device_ms", "history_ms",
       "ledger_ms", "checkpoint_ms", "host_pulls", "untraced_gap_ms",
       "compile_s")
WANT = {"select_device_ms": 0.45, "exchange_device_ms": 0.45,
        "update_device_ms": 0.7, "announce_device_ms": 0.2,
        "evaluate_device_ms": 0.1, "history_ms": 0.9, "ledger_ms": 0.35,
        "checkpoint_ms": 0.5, "host_pulls": 10.5, "untraced_gap_ms": 1.0,
        "compile_s": 0.008}


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        fx = json.load(f)
    fx["events"] = {k: [tuple(e) for e in v]
                    for k, v in fx["events"].items()}
    return fx


def _ctx(fx, periods=2):
    import devtrace as tr
    return {"events": fx["events"], "window": tr.window(fx["events"]),
            "periods_s": [0.004] * periods}


def _read(ctx, names=NEW):
    cell = {"per_layer": [{"name": n, "unit": "u"} for n in names]}
    return {k: v["value"] for k, v in run.per_layer(cell, ctx).items()}


def test_metric_readers_on_the_fixture(recorded, monkeypatch):
    monkeypatch.setattr(progspans, "snapshot", lambda: recorded["snapshot"])
    monkeypatch.setattr(progspans, "scopes", lambda: recorded["scopes"])
    got = _read(_ctx(recorded))
    assert set(got) == set(NEW)
    for name, want in WANT.items():
        assert got[name] == pytest.approx(want), name


def test_phases_cover_the_period_program(recorded, monkeypatch):
    monkeypatch.setattr(progspans, "scopes", lambda: recorded["scopes"])
    got = _read(_ctx(recorded), [n for n in NEW if n.endswith("device_ms")]
                + ["segment_device_ms", "host_gap_ms"])
    phases = sum(v for k, v in got.items() if k != "segment_device_ms"
                 and k != "host_gap_ms")
    assert got["segment_device_ms"] == pytest.approx(2.0)
    assert got["host_gap_ms"] == pytest.approx(2.8)
    assert phases == pytest.approx(1.9)
    per_phase = progspans.phase_device_ms(recorded["events"],
                                          recorded["scopes"],
                                          *_ctx(recorded)["window"])
    assert per_phase[None] == pytest.approx(0.1)     # add.8, unscoped
    assert sum(per_phase.values()) == pytest.approx(2.0)


def test_last_periods_are_the_traced_ones(recorded):
    snap = recorded["snapshot"]
    groups = progspans.periods(snap, 2)
    assert [g[0]["args"]["period"] for g in groups] == [1, 2]
    assert len(groups[1]) == 9          # the period and 8 spans under it
    assert progspans.periods(snap, 0) == []
    assert progspans.per_period_ms(snap, 3, lambda n: n == "period.log") \
        == pytest.approx((0.5 + 0.1 + 0.1) / 3)
    # gaps 0-1 and 1-2: (0.5 + 0.5 + 0.2) + (1.5 + 0.3), two gaps
    assert progspans.between_ms(snap, 3) == pytest.approx(3.0 / 2)
    assert progspans.between_ms(snap, 1) is None


def test_a_program_without_the_recorder_reports_nothing(recorded,
                                                       monkeypatch):
    monkeypatch.setattr(progspans, "_spans_module", lambda: None)
    assert progspans.snapshot() is None and progspans.scopes() is None
    assert _read(_ctx(recorded)) == {}


def test_the_running_program_is_read(recorded):
    """Without a fixture the readers take the recorder of the program
    running in this process."""
    snap = progspans.snapshot()
    assert set(snap) == {"spans", "totals", "counters"}
    assert isinstance(progspans.scopes(), dict)
