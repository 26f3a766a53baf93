"""A configuration whose client reads token ids and trains a head on
frozen weights that all clients share, taken from its own files alone.

The fixture tokens-v97 (bench/tests/fixtures: vocabulary 97, 12 ids a
row, 3 classes, a frozen embedding table, a trainable head and its
own training FLOPs) is added to a copy of the benchmark as a later
configuration would be: its two files, and entries in BENCHMARK.json
beside the traffic mixes already there. It then runs on the CPU
through the traffic, the program's set-up, both drivers and the
reference, with no other file of the harness changed. The program has
no token client yet and reads the ids through its "mlp" kind, so its
numbers are not compared with the reference's here.
"""
import io
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import BENCH, TINY, harness  # noqa: F401

import reference
import traffic

ROOT = os.path.dirname(BENCH)
NAME = "tokens-v97"
MIXES = ("personal-m10-sync", "public-m1024-sync", "service-m10-g2")
SEED = 2 ** 31 + 4242
TRAFFIC_KEYS = {"x_train", "y_train", "x_test", "y_test", "x_ref", "y_ref"}


@pytest.fixture
def root(tmp_path):
    """A checkout's benchmark with the fixture added as files and
    entries: one cell under each of MIXES."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for ext in (".json", ".py"):
        shutil.copy(os.path.join(BENCH, "tests", "fixtures", NAME + ext),
                    tmp_path / "bench" / "configs")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": NAME, "source": "https://arxiv.org/abs/2106.09685",
        "file": f"bench/configs/{NAME}.json", "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": f"{NAME}.{mix}", "config": NAME, "traffic": mix,
         "chips": 1, "why": "test"} for mix in MIXES]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(tmp_path)


def token_cell(run, root, mix="personal-m10-sync", **sizes):
    cell = run.load_cell(f"{NAME}.{mix}", root=root)
    cell["wl"] = dict(cell["wl"], **(sizes or TINY))
    return cell


def test_token_ids_are_int32_in_range_and_follow_the_seed(harness, root):
    cell = token_cell(harness, root)
    cfg, wl = cell["cfg"], cell["wl"]
    data = traffic.generate(cfg, wl, SEED)
    for part in ("train", "test", "ref"):
        x, y = data["x_" + part], data["y_" + part]
        assert x.dtype == jnp.int32 and y.dtype == jnp.int32
        assert x.shape == (4, wl[part + "_rows"], 12)
        assert 0 <= int(x.min()) and int(x.max()) < 97
        assert 0 <= int(y.min()) and int(y.max()) < 3
    again = traffic.generate(cfg, wl, SEED)
    other = traffic.generate(cfg, wl, SEED + 1)
    for k in data:
        np.testing.assert_array_equal(data[k], again[k])
    assert not np.array_equal(data["x_train"], other["x_train"])


def test_token_ids_follow_their_class_softmax(harness, root):
    """Each class's ids are distributed as the softmax of its logits at
    temperature `noise`: close to it in total variation, and far from
    the other classes' and from temperature 1."""
    cell = token_cell(harness, root, clients=4, train_rows=3000,
                      test_rows=8, ref_rows=8)
    cfg, wl = cell["cfg"], cell["wl"]
    data = traffic.generate(cfg, wl, SEED)
    logits = jax.random.normal(
        jax.random.split(traffic.seed_key(SEED), 3)[0], (3, 97))
    want = np.asarray(jax.nn.softmax(logits / wl["noise"], axis=-1))
    hot = np.asarray(jax.nn.softmax(logits, axis=-1))
    # labels are shifted by one class in the second of two clusters
    shift = np.arange(4) % wl["label_clusters"]
    cls = (np.asarray(data["y_train"]) - shift[:, None]) % 3
    x = np.asarray(data["x_train"])
    tv = lambda p, q: 0.5 * np.abs(p - q).sum()
    for c in range(3):
        ids = x[cls == c].ravel()
        assert ids.size > 20000
        got = np.bincount(ids, minlength=97) / ids.size
        assert tv(got, want[c]) < 0.04
        assert tv(got, hot[c]) > 0.1
        assert min(tv(got, want[o]) for o in range(3) if o != c) > 0.1


def test_program_parts_puts_shared_in_the_data_once(harness, root):
    cell = token_cell(harness, root)
    apply_fn, _, _, data, state = harness.program_parts(cell, SEED)
    assert set(data) == TRAFFIC_KEYS | {"shared"}
    key = jax.random.fold_in(traffic.seed_key(SEED), harness.SHARED_FOLD)
    np.testing.assert_array_equal(
        data["shared"]["embed"],
        cell["model"].init_shared(cell["cfg"], key)["embed"])
    shapes = [a.shape for a in jax.tree.leaves(data)]
    assert shapes.count((97, 12)) == 1
    assert all(a.shape[-2:] != (97, 12)
               for a in jax.tree.leaves(state.params))
    mcfg = apply_fn.args[0]
    assert (mcfg.kind, mcfg.input_shape, mcfg.hidden) == ("mlp", (12,), ())
    assert mcfg.arch == (("embed_dim", 12), ("input", "tokens"),
                         ("vocab", 97))


def test_the_program_ignores_the_shared_weights(harness, root):
    from repro.core import run_rounds, wpfed_program
    cell = token_cell(harness, root)
    apply_fn, fed, opt, data, state = harness.program_parts(cell, SEED)
    program = wpfed_program(apply_fn, opt, fed)
    plain = {k: v for k, v in data.items() if k != "shared"}
    got, _ = run_rounds(program, state, data, rounds=1)
    want, _ = run_rounds(program, state, plain, rounds=1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("mix", MIXES)
def test_both_drivers_run_with_the_shared_weights(harness, root, mix):
    cell = token_cell(harness, root, mix)
    err = io.StringIO()
    result = harness.run(cell, SEED, 0.2, False, check_out=err)
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"client_rounds_per_s",
                                      "period_p90_ms", "setup_s"}
    assert "compile events in window: 0" in err.getvalue()
    # the reference ran too; the fixture sets no limits
    assert "change_gap " in err.getvalue() and result["checks"] == {}


def test_reference_runs_in_float32_and_under_the_control(harness, root,
                                                         monkeypatch):
    cell = token_cell(harness, root)
    data = harness.cell_data(cell, SEED)
    model, seen = cell["model"], set()
    plain_apply = model.apply

    def apply(p, x, shared):
        seen.add((x.dtype, shared["embed"].dtype, p["w"][0].dtype))
        return plain_apply(p, x, shared)
    monkeypatch.setattr(model, "apply", apply)
    params = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        seen.clear()
        fed = reference.Federation(model, cell["cfg"], 4, False, dtype=dtype)
        states, rounds = fed.run(traffic.seed_key(SEED), data, 1, 1)
        assert seen == {(jnp.dtype(jnp.int32), jnp.dtype(dtype),
                         jnp.dtype(dtype))}
        assert np.isfinite(rounds[0]["loss"])
        params[dtype] = np.asarray(states[-1].params["w"][0], np.float32)
    assert not np.array_equal(params[jnp.float32], params[jnp.bfloat16])
    f32 = reference.cast_floating(data, jnp.float32)
    assert f32["shared"]["embed"] is data["shared"]["embed"]
    assert f32["x_train"] is data["x_train"]
    assert reference.cast_floating(data, jnp.bfloat16)[
        "shared"]["embed"].dtype == jnp.bfloat16


def test_round_flops_take_the_configurations_training_cost(harness, root):
    """forward 12*12 + 2*12*3 = 216, training 216 + 72 = 288 an
    example; M=10, N=9, 5 steps of 64 + 64 reference rows, 120 test
    rows."""
    cell = harness.load_cell(f"{NAME}.personal-m10-sync", root=root)
    update = 10 * 5 * (64 + 64) * 288
    forwards = 10 * 9 * 64 + 10 * 64 + 10 * 120
    assert harness.period_flops(cell, 9) == update + 216 * forwards
