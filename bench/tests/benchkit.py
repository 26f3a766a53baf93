"""Shared set-up of the benchmark's own tests: the harness modules on
the path, and a cell steered to run on the CPU at a tiny size."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    CELLS = tuple(w["name"] for w in json.load(_f)["workloads"])
TINY = {"clients": 4, "train_rows": 24, "test_rows": 8, "ref_rows": 8}


@pytest.fixture
def harness(monkeypatch):
    """bench/run.py with its chip check and compile cache steered for a
    CPU test run: the device reads as a v5e, nothing is cached."""
    import run
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "use_cache", lambda: None)
    return run


def tiny_cell(run, name, **overrides):
    """The cell at four clients."""
    cell = run.load_cell(name)
    cell["wl"] = dict(cell["wl"], **TINY, **overrides)
    return cell
