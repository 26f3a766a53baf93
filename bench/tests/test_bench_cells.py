"""BENCHMARK.json against the benchmark's contract, and the harness
finding a cell's files by name alone."""
import json
import os
import re
import shutil

import pytest

from benchkit import BENCH, CELLS

import run

ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_files(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in bench["configs"] + bench["workloads"]
             + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/") and c["reduced"] == []
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            BENCH, "workloads", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_end_to_end_metrics_and_bounds(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(e2e) == {"client_rounds_per_s", "period_p90_ms", "setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] == "host_clock" for m in e2e.values())


def test_every_config_has_a_cell(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len(CELLS) >= 1


def test_an_added_workload_is_found_by_name(tmp_path):
    """A later cell comes as files alone: a traffic file and an entry."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "workloads",
                           "personal-m10-sync.json")) as f:
        traffic = json.load(f)
    traffic["clients"] = 32
    with open(tmp_path / "bench" / "workloads" / "personal-m32-sync.json",
              "w") as f:
        json.dump(traffic, f)
    bench["workloads"].append({
        "name": "mnist-personal-m32-sync", "config": "conv2-fc128-mnist",
        "traffic": "personal-m32-sync", "chips": 1, "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell = run.load_cell("mnist-personal-m32-sync", root=str(tmp_path))
    assert cell["wl"]["clients"] == 32
    assert cell["cfg"]["name"] == "conv2-fc128-mnist"
    assert callable(cell["model"].apply)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "client_rounds_per_s", "period_p90_ms", "setup_s"}
    with pytest.raises(KeyError):
        run.load_cell("no-such-cell", root=str(tmp_path))
