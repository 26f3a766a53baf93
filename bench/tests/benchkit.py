"""Shared set-up of the benchmark's own tests: the harness modules on
the path, a cell steered to run on the CPU at a small size, and the
checks that every cell is held to, each taking the checkout's root, so
that a cell added to a copy of the benchmark meets the same checks as
the cells of BENCHMARK.json."""
import io
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = tuple(w["name"] for w in json.load(_f)["workloads"])
TINY = {"clients": 4, "train_rows": 24, "test_rows": 8, "ref_rows": 8}
# What a configuration's "cpu" block may override.
CPU_KEYS = ("model", "fed", "params")
# Client-model FLOPs of a period that a CPU run of the harness's tests
# may take: 4 x the largest of the first four cells at TINY (the
# service's, 10,613,657,600; test_bench_pins.py keeps the two equal).
CPU_FLOPS_CAP = 4 * 10_613_657_600

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# keys that name a width, which "reduced" may never list
WIDTH = re.compile(r"(^hidden$|hidden_size|intermediate|latent|_dim$|_rank$"
                   r"|head_size|expansion|experts_per_tok|state_size"
                   r"|proj.*size)")
E2E = {"client_rounds_per_s", "period_p90_ms", "setup_s"}


@pytest.fixture
def harness(monkeypatch):
    """bench/run.py with its chip check and compile cache steered for a
    CPU test run: the device reads as a v5e, nothing is cached."""
    import run
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "use_cache", lambda: None)
    return run


def cpu_config(cfg):
    """The configuration at the size its "cpu" block declares: each of
    the block's groups merged over the configuration's, its numbers in
    their place; the configuration as it is where it has no block."""
    cpu = cfg.get("cpu", {})
    unknown = set(cpu) - set(CPU_KEYS)
    if unknown:
        raise ValueError(f"{cfg['name']}: the 'cpu' block may override "
                         f"only {CPU_KEYS}, not {sorted(unknown)}")
    out = {k: v for k, v in cfg.items() if k != "cpu"}
    for k, v in cpu.items():
        out[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    return out


def tiny_cell(run, name, root=ROOT, **overrides):
    """The cell at four clients, its configuration at the size its
    "cpu" block declares. A cell that would still take more than
    CPU_FLOPS_CAP a period is refused, so that a configuration too
    large for the CPU fails here and does not time the suite out."""
    cell = run.load_cell(name, root=root)
    cell["cfg"] = cpu_config(cell["cfg"])
    cell["wl"] = dict(cell["wl"], **TINY, **overrides)
    m = cell["wl"]["clients"]
    flops = run.period_flops(
        cell, min(cell["cfg"]["fed"]["num_neighbors"], m - 1))
    if flops > CPU_FLOPS_CAP:
        raise ValueError(
            f"{name}: {flops:,} client-model FLOPs a period at four "
            f"clients, over the CPU tests' {CPU_FLOPS_CAP:,}: give "
            f"configuration {cell['cfg']['name']!r} a 'cpu' block that "
            "shrinks it (overrides of 'model', 'fed' and 'params')")
    return cell


def read_json(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------ BENCHMARK.json checks
def check_top_level(root):
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536


def check_configs(root):
    """Each configuration's file is under bench/ and its own; "reduced"
    lists at most 16 names, none of them a width, and where it lists
    any the file states the deployment that the cut stands for."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(root, c["file"]))
        reduced = c["reduced"]
        assert isinstance(reduced, list) and len(reduced) <= 16
        assert all(isinstance(k, str) and NAME.match(k)
                   and not WIDTH.search(k) for k in reduced), reduced
        if reduced:
            deployment = read_json(os.path.join(root, c["file"])).get(
                "deployment")
            assert isinstance(deployment, str) and deployment.strip(), (
                f"{c['name']} is cut ({reduced}) and states no deployment")


def check_workloads(root):
    """Each cell takes 1 or 4 chips, at most half of them (and always
    one) 4, and has its traffic and limits files."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = bench["workloads"]
    for w in cells:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            root, "bench", "workloads", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(root, "bench", "limits",
                                           w["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def check_names_units_and_files(root):
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in bench["configs"] + bench["workloads"]
             + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    check_configs(root)
    check_workloads(root)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def check_end_to_end(root):
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(e2e) == E2E
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] == "host_clock" for m in e2e.values())


def check_every_config_has_a_cell(root):
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len(bench["workloads"]) >= 1


# ------------------------------------------------------- a cell's runs
def check_cell_runs(run, name, root=ROOT):
    """The cell's run, end to end on the CPU at its small size: the
    window opens and closes inside the program's own period loop, the
    result line has the contract's keys, and the output check passes."""
    cell = tiny_cell(run, name, root)
    err = io.StringIO()
    result = run.run(cell, 2 ** 31 + 977, 0.2, False, check_out=err)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert result["correct"], err.getvalue()
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == E2E
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])
    assert "compile events in window: 0" in err.getvalue()
    json.dumps(result)
    lines = err.getvalue().strip().splitlines()
    assert len(lines) >= len(result["checks"])
    assert all(line.startswith("check ")
               for line in lines[-len(result["checks"]):])


def check_control_fails(run, name, root=ROOT):
    """The plain reference computed in bfloat16, put in the program's
    place, fails the cell's output check."""
    import compare
    import control
    cell = tiny_cell(run, name, root)
    found = control.readings(cell, 2 ** 31 + 51, kinds=("control",))
    checks = compare.judge(found["control"], cell["limits"])
    assert checks
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def unchanged_state(monkeypatch):
    """The local update returns the parameters and optimizer state it
    was given."""
    from repro.core import protocol
    orig = protocol.batched_local_update

    def update(apply_fn, optimizer, fed, params, opt_state, *rest):
        _, _, metrics = orig(apply_fn, optimizer, fed, params, opt_state,
                             *rest)
        return params, opt_state, metrics
    monkeypatch.setattr(protocol, "batched_local_update", update)


def half_batch(monkeypatch):
    """Each local minibatch's loss is the mean over its first half."""
    from repro.core import distill
    orig = distill.combined_loss

    def loss(apply_fn, params, batch, *rest, **kw):
        n = batch["x"].shape[0] // 2
        return orig(apply_fn, params,
                    {"x": batch["x"][:n], "y": batch["y"][:n]}, *rest, **kw)
    monkeypatch.setattr(distill, "combined_loss", loss)


FAULTS = (unchanged_state, half_batch)


def check_fault_fails(run, monkeypatch, name, fault, root=ROOT):
    """A run whose timed path has `fault` planted underneath comes out
    not correct."""
    fault(monkeypatch)
    cell = tiny_cell(run, name, root)
    result = run.run(cell, 2 ** 31 + 7, 0.2, False,
                     check_out=io.StringIO())
    assert result["correct"] is False
    assert result["failed"] >= 1
