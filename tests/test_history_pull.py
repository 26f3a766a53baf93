"""`core.rounds.extract_history` pulls a period's per-round scalar
metrics to the host in one batched `jax.device_get`:

  * the history is bit-identical to the per-scalar loop it replaced
    (copied below as the reference): same keys in the same order, same
    values, same Python types, same "round";
  * `jax.device_get` is called once, with the 1-D leaves only: a 2-D
    leaf (neighbor ids, masks, per-client vectors) stays on the device;
  * `host_pulls` counts one pull per array, not one per scalar.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import evaluate, init_state, wpfed_program
from repro.core.rounds import extract_history, make_segment_fn


def reference_history(metrics, r0, length):
    """The per-scalar loop: an eager `v[i]` and an `int()`/`float()`
    sync for each round and each 1-D metric."""
    history = []
    for i in range(length):
        entry = {}
        for k, v in metrics.items():
            if getattr(v, "ndim", None) == 1:
                is_int = jnp.issubdtype(v.dtype, jnp.integer)
                entry[k] = int(v[i]) if is_int else float(v[i])
        entry["round"] = r0 + i
        history.append(entry)
    return history


def assert_identical(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert list(g) == list(w)                     # keys and order
        for k in w:
            assert type(g[k]) is type(w[k]), k
            assert repr(g[k]) == repr(w[k]), k        # bit for bit


def _metrics(length):
    """Mixed dtypes in a deliberately unsorted key order, with a 2-D
    leaf and a 0-D one among the per-round scalars."""
    rs = np.random.RandomState(length)
    return {
        "mean_loss": jnp.asarray(rs.rand(length) / 3, jnp.float32),
        "agree": jnp.asarray(rs.rand(length) > 0.5),             # bool
        "n_active": jnp.asarray(rs.randint(-5, 9, length), jnp.int32),
        "neighbor_ids": jnp.asarray(rs.randint(0, 9, (length, 4)),
                                    jnp.int32),
        "acc": jnp.asarray(rs.rand(length), jnp.bfloat16),
        "flips": jnp.asarray(rs.randint(0, 2**31, length), jnp.uint32),
        "odd": jnp.asarray([np.nan, np.inf, -0.0][:length], jnp.float32),
        "lr": jnp.float32(1e-3),                                 # 0-D
    }


@pytest.fixture
def pulls_seen(monkeypatch):
    """Every tree that `jax.device_get` is handed, in order."""
    seen = []
    device_get = jax.device_get

    def recording(tree):
        seen.append(tree)
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", recording)
    return seen


@pytest.mark.parametrize("length", [1, 3])
def test_batched_pull_matches_the_per_scalar_loop(length):
    metrics = _metrics(length)
    want = reference_history(metrics, 7, length)
    got = extract_history(metrics, 7, length)
    assert_identical(got, want)
    assert [h["round"] for h in got] == list(range(7, 7 + length))
    assert list(got[0]) == ["mean_loss", "agree", "n_active", "acc",
                            "flips", "odd", "round"]
    assert {k: type(v) for k, v in got[0].items()} == {
        "mean_loss": float, "agree": float, "n_active": int, "acc": float,
        "flips": int, "odd": float, "round": int}


@pytest.mark.parametrize("length", [1, 3])
def test_one_device_get_of_the_1d_leaves_only(length, pulls_seen):
    metrics = _metrics(length)
    extract_history(metrics, 0, length)
    assert len(pulls_seen) == 1
    (tree,) = pulls_seen
    assert set(tree) == {"mean_loss", "agree", "n_active", "acc", "flips",
                         "odd"}
    assert all(v.ndim == 1 for v in tree.values())
    assert not any(v is metrics["neighbor_ids"] for v in tree.values())
    assert not any(v is metrics["lr"] for v in tree.values())


@pytest.mark.parametrize("length", [1, 3])
def test_host_pulls_counts_arrays_not_scalars(length):
    before = spans.snapshot()["counters"].get(spans.HOST_PULLS, 0)
    extract_history(_metrics(length), 0, length)
    after = spans.snapshot()["counters"].get(spans.HOST_PULLS, 0)
    assert after - before == 6


def test_no_scalars_pulls_nothing(pulls_seen):
    metrics = {"neighbor_ids": jnp.zeros((2, 3), jnp.int32)}
    assert extract_history(metrics, 4, 2) == [{"round": 4}, {"round": 5}]
    assert not any(pulls_seen)                      # nothing to pull


@pytest.mark.parametrize("length", [1, 3])
def test_period_program_history_is_bit_identical(tiny_fed, length,
                                                 pulls_seen):
    """A real WPFed period (global round, then gossip epochs), whose
    stacked metrics carry the 2-D neighbor ids beside the scalars."""
    f = tiny_fed
    fed = dataclasses.replace(f["fed"], num_clients=4)
    data = {k: v[:4] for k, v in f["data"].items()}
    state = init_state(f["apply_fn"], f["init_fn"], f["opt"], fed,
                       jax.random.PRNGKey(1))
    program = wpfed_program(f["apply_fn"], f["opt"], fed)

    def eval_fn(st, d):
        return {"acc": evaluate(f["apply_fn"], st, d)["mean_acc"]}

    seg = jax.jit(make_segment_fn(program, length, eval_fn=eval_fn))
    _, metrics = seg(state, data)
    jax.block_until_ready(metrics)
    assert any(getattr(v, "ndim", 0) > 1 for v in metrics.values())
    want = reference_history(metrics, 3, length)
    pulls_seen.clear()
    got = extract_history(metrics, 3, length)
    (tree,) = pulls_seen
    assert_identical(got, want)
    # 7 round metrics ("round" among them, overwritten by r0 + i) and acc
    assert len(got[0]) == 8 and len(tree) == 8
    assert all(v.ndim == 1 for v in tree.values())
