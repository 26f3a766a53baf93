"""BENCHMARK.json against the benchmark's contract, and the harness
finding a cell's files by name alone."""
import json
import os
import shutil

import pytest

import benchkit
from benchkit import BENCH, ROOT

import run


def test_top_level_keys():
    benchkit.check_top_level(ROOT)


def test_names_units_and_files():
    benchkit.check_names_units_and_files(ROOT)


def test_end_to_end_metrics_and_bounds():
    benchkit.check_end_to_end(ROOT)


def test_every_config_has_a_cell():
    benchkit.check_every_config_has_a_cell(ROOT)


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


def _cut(bench, cfg):
    bench["configs"][0]["reduced"] = ["num_hidden_layers"]
    cfg.pop("deployment", None)


def _width(bench, cfg):
    bench["configs"][0]["reduced"] = ["intermediate_size"]
    cfg["deployment"] = "2 chips share each layer"


def _two_chips(bench, cfg):
    bench["workloads"][0]["chips"] = 2


def _four_chip_majority(bench, cfg):
    for w in bench["workloads"][:len(bench["workloads"]) // 2 + 1]:
        w["chips"] = 4


@pytest.mark.parametrize("breach", [_cut, _width, _two_chips,
                                    _four_chip_majority],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_contract_checks_refuse_a_breach(tmp_path, breach):
    """A cut with no deployment, a width in "reduced", a cell on 2
    chips, and 4 chips in more than half the cells are refused."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    cfg_path = tmp_path / bench["configs"][0]["file"]
    with open(cfg_path) as f:
        cfg = json.load(f)
    benchkit.check_names_units_and_files(str(tmp_path))
    breach(bench, cfg)
    for path, obj in ((tmp_path / "BENCHMARK.json", bench), (cfg_path, cfg)):
        with open(path, "w") as f:
            json.dump(obj, f)
    with pytest.raises(AssertionError):
        benchkit.check_names_units_and_files(str(tmp_path))
