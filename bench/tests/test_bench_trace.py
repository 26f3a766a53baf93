"""The trace reduction on a small trace excerpt, against values worked
out by hand from its events (times in ns):

  window 900..4500 (3600)
  ops, merged: 1000-1900, 2000-2300, 2600-2700, 3000-3500, 3600-4200,
               4300-4400  -> busy 900+300+100+500+600+100 = 2500
  idle: 900-1000, 1900-2000, 2300-2600, 2700-3000, 3500-3600,
        4200-4300, 4400-4500 -> 1100 = 3600 - 2500
  period programs: 1000-2400 and 3000-4250
    busy inside: 900+300 = 1200 and 500+600 = 1100 -> mean 1150
    host gap between them, 2400-3000: idle 2400-2600 and 2700-3000 = 500
  lsh_project_sums_batched: 200 + 200 = 400 in 2 calls; fused_exchange:
  300 + 100 = 400 in 2 (fusion.2 reads the exchange's output: no match)
The op events carry whole HLO instruction texts, as the device trace
names them, and a while loop that spans the first period's ops.
"""
import os

import pytest

from benchkit import BENCH

import devtrace as tr

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "trace_excerpt.json")


@pytest.fixture(scope="module")
def events():
    ev = tr.read(FIXTURE)
    ev["ops"] = [(tr.op_name(n), s, e) for n, s, e in ev["ops"]]
    return ev


def test_op_names_are_instruction_names(events):
    assert [n for n, _, _ in events["ops"][:3]] == [
        "while.165", "fusion.1", "lsh_project_sums_batched.1"]


def test_window_busy_and_idle_share(events):
    lo, hi = tr.window(events)
    assert (lo, hi) == (900, 4500)
    assert tr.busy_ns(events, lo, hi) == 2500
    assert sum(e - s for s, e in tr.idle_gaps(events, lo, hi)) == 1100
    assert 1 - tr.busy_ns(events, lo, hi) / (hi - lo) == pytest.approx(
        1100 / 3600)


def test_host_gap_and_segment_busy(events):
    lo, hi = tr.window(events)
    assert tr.host_gaps_ns(events, lo, hi) == [500]
    assert tr.segment_busy_ns(events, lo, hi) == [1200, 1100]


def test_kernel_time(events):
    lo, hi = tr.window(events)
    assert tr.kernel_ns(events, "lsh_project_sums_batched", lo, hi) == (400, 2)
    assert tr.kernel_ns(events, "fused_exchange", lo, hi) == (400, 2)
    assert tr.kernel_ns(events, "no_such_kernel", lo, hi) == (0, 0)


def test_breakdown_attributes_gaps_to_host_spans(events):
    lo, hi = tr.window(events)
    out = tr.breakdown(events, lo, hi, top=3)
    assert out["device_ops"][0] == ["fusion.1", 900 / 1e9]
    assert out["idle_gaps"][0] == ["bench.publish", 300 / 1e9]
    assert [g[1] for g in out["idle_gaps"]] == [300 / 1e9, 300 / 1e9,
                                                100 / 1e9]
    assert out["idle_gaps"][1][0] == "after bench.publish"


def test_metric_readers_on_the_excerpt(events):
    import run
    import work
    ctx = {"events": events, "window": tr.window(events),
           "periods_s": [2.0, 2.0], "period_flops": 197e12,
           "lsh": (0.0, 819e9 * 100e-9), "exchange": (0.0, 0.0),
           "peaks": work.peaks("TPU v5 lite"), "memory_peak_bytes": 2e9}
    cell = {"per_layer": [{"name": n, "unit": "u"} for n in (
        "host_gap_ms", "segment_device_ms", "period_mfu", "lsh_roofline",
        "exchange_roofline", "peak_hbm_gb")]}
    got = {k: v["value"] for k, v in run.per_layer(cell, ctx).items()}
    assert got["host_gap_ms"] == pytest.approx(500 / 1e6)
    assert got["segment_device_ms"] == pytest.approx(1150 / 1e6)
    assert got["period_mfu"] == pytest.approx(50.0)
    # 100 ns of HBM traffic per call, 2 calls, 400 ns measured
    assert got["lsh_roofline"] == pytest.approx(50.0)
    assert "exchange_roofline" not in got        # no work, no reading
    assert got["peak_hbm_gb"] == pytest.approx(2.0)
