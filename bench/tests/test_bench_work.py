"""Work counts: the configurations' forward FLOPs against XLA's own
count of the jitted forward, and the kernels' operations and bytes
against the formulas the kernel micro-benchmarks use
(benchmarks/kernel_micro.py: LSH 2*M*P*bits and M*P*4 bytes)."""
import os

import pytest

from benchkit import BENCH

import run
import work


@pytest.mark.parametrize("name", ["conv2-fc128-mnist", "tcn3-w32-aecg"])
def test_forward_flops_match_xla_cost_analysis(name):
    import jax
    import jax.numpy as jnp
    cfg = run._json(os.path.join(BENCH, "configs", name + ".json"))
    model = run._module(os.path.join(BENCH, "configs", name + ".py"),
                        "cfg_" + name.replace("-", "_"))
    params = model.init(cfg, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg["params"]
    batch = 8
    x = jnp.zeros((batch,) + tuple(cfg["model"]["input_shape"]))
    xla = jax.jit(model.apply).lower(params, x).compile().cost_analysis()
    ours = model.forward_flops(cfg) * batch
    # XLA also counts bias adds, ReLUs and pooling (under 2% here);
    # both leave out taps on the zero padding
    assert 0.97 * xla["flops"] <= ours <= xla["flops"]


def test_lsh_work_matches_the_micro_benchmark_formula():
    m, p, bits = 1024, 10594, 256
    flops, nbytes = work.lsh_work(m, p, bits)
    assert flops == 2.0 * m * p * bits
    assert nbytes == m * p * 4 + m * bits // 8


def test_exchange_work_by_hand():
    m, n, r, c = 128, 12, 64, 10
    flops, nbytes = work.exchange_work(m, n, r, c)
    assert flops == 10 * 983040
    web, own, labels = 983040 * 4, 81920 * 4, 8192 * 4
    target, lij_mask, has = 81920 * 4, 1536 * 5, 128
    assert nbytes == web + own + labels + target + lij_mask + has


def test_round_flops_by_hand():
    fed = {"local_batch": 64, "local_steps": 5}
    wl = {"ref_rows": 48, "train_rows": 201, "test_rows": 87}
    per_fwd = 1024 * 5 * (64 + 48) * 3 + 1024 * 48 + 1024 * 87
    assert work.round_flops(7, 1024, 10, fed, wl, True) == 7 * per_fwd
    personal = 1024 * 5 * 112 * 3 + 1024 * 10 * 48 + 1024 * 48 + 1024 * 87
    assert work.round_flops(7, 1024, 10, fed, wl, False) == 7 * personal
    # a configuration that counts its own training cost (a frozen base)
    trained = 1024 * 5 * 112 * 11 + 7 * (1024 * 48 + 1024 * 87)
    assert work.round_flops(7, 1024, 10, fed, wl, True,
                            train_flops=11) == trained


def test_roofline_bound_is_the_larger_time():
    pk = work.peaks("TPU v5 lite")
    t, bound = work.min_seconds(197e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.min_seconds(1.0, 819e9, pk)
    assert bound == "memory" and t == pytest.approx(1.0)
