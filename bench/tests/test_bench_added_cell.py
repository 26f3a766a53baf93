"""A cell added to the benchmark as files and entries alone, held to
every check that the cells of BENCHMARK.json are held to.

`fixtures/added/` holds what a later change would add, laid out as it
would lie under bench/: a configuration (configs/mlp3-w2048: the
program's "mlp" client and its plain reference, which compute the same
function; cut in depth, so its "reduced" is not empty and its file
states the deployment; a "cpu" block that shrinks it for these tests;
a `layer_work`), its traffic mix, its limits and a per-layer metric
that reads ctx["layer_work"]; entries.json holds its entries in
BENCHMARK.json. They are copied into a copy of the benchmark, in
which no file that was there changes. At its own size the fixture is
too large for a CPU run of these tests (about 2 x benchkit's
CPU_FLOPS_CAP a period at four clients).
"""
import json
import os
import shutil
import types

import jax
import pytest

import benchkit
from benchkit import (BENCH, FAULTS, ROOT, harness,  # noqa: F401
                      read_json, tiny_cell)
from test_bench_pins import PINS, check_pinned

import devtrace as tr
import work

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "added")
PARTS = ("configs", "workloads", "limits", "metrics")
CONFIG, CELL, METRIC = ("mlp3-w2048", "mlp3-personal-m12-gossip2",
                        "dense_tflops")
SEED = 2 ** 31 + 1717


def _files(top):
    """Every file under `top` but caches, by its path under `top`."""
    out = {}
    for d, dirs, names in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = f.read()
    return out


def checkout(top):
    """A copy of the benchmark at `top` with the fixture added: its
    files copied in, none over a file that is there, and its entries
    appended to BENCHMARK.json."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), top)
    shutil.copytree(BENCH, os.path.join(top, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for part in PARTS:
        for name in os.listdir(os.path.join(FIXTURE, part)):
            dest = os.path.join(top, "bench", part, name)
            assert not os.path.exists(dest), dest
            shutil.copy(os.path.join(FIXTURE, part, name), dest)
    bench = read_json(os.path.join(top, "BENCHMARK.json"))
    for key, entries in read_json(os.path.join(FIXTURE,
                                           "entries.json")).items():
        bench[key] += entries
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    top = str(tmp_path_factory.mktemp("checkout"))
    checkout(top)
    return top


def test_only_files_and_entries_were_added(root):
    old, new = _files(BENCH), _files(os.path.join(root, "bench"))
    assert all(new[path] == data for path, data in old.items())
    added = sorted(set(new) - set(old))
    assert added == sorted(os.path.join(part, name) for part in PARTS
                           for name in os.listdir(os.path.join(FIXTURE,
                                                               part)))
    was = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    now = read_json(os.path.join(root, "BENCHMARK.json"))
    assert set(now) == set(was)
    for key in was:
        if isinstance(was[key], list):
            assert now[key][:len(was[key])] == was[key]
        else:
            assert now[key] == was[key]


def test_the_contract_checks_pass(root):
    benchkit.check_top_level(root)
    benchkit.check_names_units_and_files(root)
    benchkit.check_end_to_end(root)
    benchkit.check_every_config_has_a_cell(root)
    entry = {c["name"]: c for c in read_json(os.path.join(
        root, "BENCHMARK.json"))["configs"]}[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]


def test_the_pins_do_not_apply(root):
    cells = [w["name"] for w in read_json(os.path.join(
        root, "BENCHMARK.json"))["workloads"]]
    check_pinned(cells)
    assert CELL in cells and CELL not in PINS


def test_the_cell_runs_correct_at_its_cpu_size(harness, root):
    benchkit.check_cell_runs(harness, CELL, root)


def test_the_bfloat16_control_fails(harness, root):
    benchkit.check_control_fails(harness, CELL, root)


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(harness, monkeypatch, root,
                                            fault):
    benchkit.check_fault_fails(harness, monkeypatch, CELL, fault, root)


def _context(run, cell):
    """trace_context of the cell over the recorded trace excerpt, with
    two traced periods of 2 s each."""
    events = tr.read(os.path.join(BENCH, "tests", "fixtures",
                                  "trace_excerpt.json"))
    clock = run.Clock(0, 0, None)
    clock.stamps = [0.0, 2.0, 4.0]
    fed = types.SimpleNamespace(
        num_neighbors=cell["cfg"]["fed"]["num_neighbors"])
    return run.trace_context(cell, events, clock,
                             {"memory_peak_bytes": 1},
                             work.peaks("TPU v5 lite"), fed)


def test_the_metric_reads_the_configurations_count(harness, root):
    """A traced run hands the per-layer readers the configuration's own
    `layer_work`; the added metric, found by its name in the copy,
    reads it. For a cell whose configuration counts none, the context
    holds {} and the metric reads nothing."""
    cell = harness.load_cell(CELL, root=root)
    ctx = _context(harness, cell)
    flops, nbytes = ctx["layer_work"]["dense"]
    # 12 clients x 5 steps x 2 rounds, 64 + 64 rows, 3 forward costs
    assert flops == 120 * 128 * 3 * cell["model"].forward_flops(cell["cfg"])
    assert nbytes == 120 * 3 * 4 * 10020874
    got = harness.per_layer(cell, ctx)
    assert got == {METRIC: {"value": flops * 2 / 4.0 / 1e12,
                            "unit": "TFLOP/s"}}

    plain = harness.load_cell(next(iter(PINS)))
    ctx = _context(harness, plain)
    assert ctx["layer_work"] == {}
    assert harness.per_layer({"per_layer": cell["per_layer"],
                              "bench": cell["bench"]}, ctx) == {}


def _param_count(state):
    return sum(a[0].size for a in jax.tree.leaves(state.params))


def test_the_cpu_block_never_reaches_a_chip_run(harness, root):
    """load_cell and program_parts, which every run on the chip goes
    through, build the configuration at its own size; tiny_cell alone
    builds the size that the "cpu" block declares."""
    cell = harness.load_cell(CELL, root=root)
    assert cell["cfg"]["model"]["hidden"] == [2048, 2048, 2048]
    # two clients and few rows, so that the full-size model fits a CPU
    # test; the rows do not touch the model's size
    cell["wl"] = dict(cell["wl"], clients=2, train_rows=8, test_rows=4,
                      ref_rows=4)
    apply_fn, *_, state = harness.program_parts(cell, SEED)
    assert apply_fn.args[0].hidden == (2048, 2048, 2048)
    assert _param_count(state) == cell["cfg"]["params"] == 10020874

    small = tiny_cell(harness, CELL, root)
    assert "cpu" not in small["cfg"]
    apply_fn, *_, state = harness.program_parts(small, SEED)
    assert apply_fn.args[0].hidden == (32, 32)
    assert _param_count(state) == small["cfg"]["params"] == 26506


def test_a_configuration_too_large_for_the_cpu_is_refused(harness,
                                                          tmp_path):
    """The fixture with its "cpu" block taken out: tiny_cell refuses it
    and names the block."""
    checkout(str(tmp_path))
    path = tmp_path / "bench" / "configs" / (CONFIG + ".json")
    cfg = read_json(path)
    del cfg["cpu"]
    with open(path, "w") as f:
        json.dump(cfg, f)
    cell = harness.load_cell(CELL, root=str(tmp_path))
    cell["wl"] = dict(cell["wl"], **benchkit.TINY)
    flops = harness.period_flops(cell, 3)
    assert flops > benchkit.CPU_FLOPS_CAP
    with pytest.raises(ValueError, match="'cpu' block"):
        tiny_cell(harness, CELL, str(tmp_path))
