"""The local update a chunk of clients at a time (`core.protocol`).

  * `_chunked_local_update` at widths 3 and M (M = 7, so width 3 leaves
    a remainder chunk of 1) gives the parameters, optimizer state and
    train metrics of the one-client map (width 1) at f32 tolerance,
    for an MLP and a tiny TCN;
  * `update_phase`'s `participate` mask still freezes clients bitwise
    when the update runs chunked;
  * `update_chunk` is a function of (backend, M, one client's parameter
    shapes): 1 on the CPU and where conv kernels hold less than half
    of the parameters, else the fewest clients whose narrowest conv
    width fills 256 lanes, capped at M;
  * the public `batched_local_update` counts the width it chose under
    `update.chunk`, and on the CPU that width is 1.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.paper_models import (ClientModelConfig, FedConfig,
                                        aecg_tcn, mnist_cnn)
from repro.core import protocol
from repro.core.exchange import ExchangeResult
from repro.models import apply_client_model, init_client_model
from repro.optim import adam

M, N_LOC, N_REF, CLASSES = 7, 24, 10, 3
MODELS = {
    "mlp": ClientModelConfig("t-mlp", "mlp", (6,), CLASSES, hidden=(16,)),
    "tcn": ClientModelConfig("t-tcn", "tcn", (12, 1), CLASSES,
                             hidden=(8, 8), kernel_size=3),
}
FED = FedConfig(num_clients=M, num_neighbors=3, top_k=2, local_steps=2,
                local_batch=8, lsh_bits=128, lr=1e-2)


def _shapes(mcfg):
    params = jax.eval_shape(
        functools.partial(init_client_model, mcfg), jax.random.PRNGKey(0))
    return [x.shape for x in jax.tree.leaves(params)]


@functools.lru_cache(maxsize=None)
def _case(kind):
    """One model's stacked clients, data, targets and keys (M = 7)."""
    mcfg = MODELS[kind]
    rs = np.random.RandomState(3)
    shape = mcfg.input_shape
    data = {
        "x_train": jnp.asarray(rs.randn(M, N_LOC, *shape), jnp.float32),
        "y_train": jnp.asarray(rs.randint(0, CLASSES, (M, N_LOC)),
                               jnp.int32),
        "x_ref": jnp.asarray(rs.randn(M, N_REF, *shape), jnp.float32),
        "y_ref": jnp.asarray(rs.randint(0, CLASSES, (M, N_REF)),
                             jnp.int32),
    }
    keys = jax.random.split(jax.random.PRNGKey(1), M)
    params = jax.vmap(functools.partial(init_client_model, mcfg))(keys)
    opt = adam(FED.lr)
    opt_state = jax.vmap(opt.init)(params)
    target = jnp.asarray(rs.randn(M, N_REF, CLASSES), jnp.float32)
    has_target = jnp.asarray(rs.rand(M) < 0.6)
    upd_keys = jax.random.split(jax.random.PRNGKey(2), M)
    apply_fn = functools.partial(apply_client_model, mcfg)
    return apply_fn, opt, params, opt_state, data, target, has_target, \
        upd_keys


def _run(kind, width):
    apply_fn, opt, params, opt_state, data, target, has_target, keys = \
        _case(kind)
    fn = jax.jit(functools.partial(protocol._chunked_local_update,
                                   apply_fn, opt, FED, width=width))
    return fn(params, opt_state, data, target, has_target, keys)


@pytest.mark.parametrize("kind", sorted(MODELS))
@pytest.mark.parametrize("width", [3, M])
def test_chunked_update_matches_one_client_map(kind, width):
    want = _run(kind, 1)
    got = _run(kind, width)
    names = ("params", "opt_state", "train_metrics")
    for name, g, w in zip(names, got, want):
        g_leaves, w_leaves = jax.tree.leaves(g), jax.tree.leaves(w)
        assert jax.tree.structure(g) == jax.tree.structure(w), name
        for a, b in zip(g_leaves, w_leaves):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=name)
    # the update moved every client: a width that dropped a chunk (the
    # remainder above all) would leave its clients at their start
    start = jax.tree.leaves(_case(kind)[2])
    for a, s in zip(jax.tree.leaves(got[0]), start):
        moved = np.abs(np.asarray(a) - np.asarray(s)).reshape(M, -1)
        assert (moved.max(axis=1) > 0).all()


def test_participate_freezes_clients_bitwise_when_chunked(monkeypatch):
    apply_fn, opt, params, opt_state, data, target, has_target, keys = \
        _case("tcn")
    monkeypatch.setattr(protocol, "update_chunk", lambda *a: 3)
    exch = ExchangeResult(
        l_ij=None, valid_mask=None, target_ref=target,
        has_target=has_target)
    participate = jnp.asarray([True, False, True, True, False, True,
                               False])
    new_p, new_s, _ = jax.jit(
        lambda p, s, part: protocol.update_phase(
            apply_fn, opt, FED, p, s, data, exch,
            jax.random.PRNGKey(5), participate=part))(
        params, opt_state, participate)
    frozen = ~np.asarray(participate)
    for new, old in zip(jax.tree.leaves((new_p, new_s)),
                        jax.tree.leaves((params, opt_state))):
        new, old = np.asarray(new), np.asarray(old)
        np.testing.assert_array_equal(new[frozen], old[frozen])
        moved = np.abs(new[~frozen] - old[~frozen]).reshape(
            (~frozen).sum(), -1)
        assert (moved.max(axis=1) > 0).all()


def _tcn_like(conv_out, dense_out):
    """A conv of width `conv_out` twice, then a dense head."""
    return [(5, 1, conv_out), (5, conv_out, conv_out), (conv_out,),
            (conv_out, dense_out), (dense_out,)]


@pytest.mark.parametrize("backend,m,shapes,want", [
    ("cpu", 1024, "aecg", 1),       # XLA-CPU keeps the one-client map
    ("tpu", 1024, "aecg", 8),       # width 32: 8 clients fill 256 lanes
    ("tpu", 10, "aecg", 8),         # M = 10: a chunk of 8, then 2
    ("tpu", 3, "aecg", 3),          # capped at M
    ("tpu", 128, "mnist", 1),       # 96% of its parameters are dense
    ("tpu", 10, "mnist", 1),
    ("tpu", 1024, "mlp", 1),        # no conv: dense layers gain nothing
    ("tpu", 1024, _tcn_like(8, 2), 32),
    ("tpu", 1024, _tcn_like(48, 2), 6),
    ("tpu", 1024, _tcn_like(128, 2), 2),
    ("tpu", 1024, _tcn_like(256, 2), 1),
    ("tpu", 1024, _tcn_like(512, 2), 1),
    # conv 5*32 + 5*32*32 = 5,280 of 5,444 parameters: chunked
    ("tpu", 1024, _tcn_like(32, 4), 8),
    # dense 32*200 = 6,400 of 11,912: one client
    ("tpu", 1024, _tcn_like(32, 200), 1),
])
def test_update_chunk_rule(backend, m, shapes, want):
    if isinstance(shapes, str):
        shapes = _shapes({"aecg": aecg_tcn(), "mnist": mnist_cnn(),
                          "mlp": MODELS["mlp"]}[shapes])
    assert protocol.update_chunk(backend, m, shapes) == want


def test_public_update_counts_its_width_and_keeps_one_on_cpu():
    apply_fn, opt, params, opt_state, data, target, has_target, keys = \
        _case("tcn")
    before = spans.snapshot()["counters"].get(protocol.UPDATE_CHUNK, 0)
    got = jax.jit(functools.partial(protocol.batched_local_update,
                                    apply_fn, opt, FED))(
        params, opt_state, data, target, has_target, keys)
    after = spans.snapshot()["counters"].get(protocol.UPDATE_CHUNK, 0)
    assert jax.default_backend() == "cpu"
    assert after - before == 1
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(_run("tcn", 1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
