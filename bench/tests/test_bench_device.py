"""The device guard and the peaks table: no TPU, no result; an
unknown device kind is an error, not a default."""
import json
import os
import subprocess
import sys

import pytest

from benchkit import BENCH, CELLS

import run
import work


def test_no_tpu_raises():
    with pytest.raises(run.NoChip):
        run.device_info(1)


def test_main_without_a_chip_exits_nonzero_and_prints_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed",
                     "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_command_outside_a_checkout_fails(tmp_path):
    """A directory with only BENCHMARK.json and bench/ holds no program."""
    import shutil
    root = os.path.dirname(BENCH)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["hbm_bytes"] == 16e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_every_peak_row_is_complete():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    for row in table.values():
        assert {"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes",
                "source"} <= set(row)
