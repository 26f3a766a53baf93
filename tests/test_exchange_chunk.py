"""The personal exchange's forwards a few (client, neighbor) pairs at a
time (`core.protocol`).

  * `_mapped_ref_logits` at widths 1, N, N+1 (one client's row of
    pairs) and 8 (M = 6 and N = M - 1 = 5, so N and 8 leave a
    remainder chunk) gives the own logits and the logit web of the
    double ``vmap`` over gathered neighbor parameters, for a tiny CNN
    and a tiny TCN. That old form is kept here as the oracle only;
  * slots that are masked out or padded, with ids below 0 or past M,
    see the model ``p[ids]`` picks, so `exchange_phase` gives the
    oracle's `valid_mask` and `l_ij`;
  * the width, `lane_chunk` of (backend, pairs, one client's parameter
    shapes), is pinned for the benchmark's two configurations: 8 pairs
    a step on a TPU for both (their narrowest conv is 32 wide), 1 on
    the CPU and for a model without convs;
  * the personal `exchange_phase` counts its width under
    `exchange.chunk` (1 on the CPU); the public one counts nothing.
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
from repro.core.exchange import all_in_one_exchange, public_ref_logits
from repro.models import apply_client_model, init_client_model

M, N, R, CLASSES = 6, 5, 7, 3
MODELS = {
    "cnn": ClientModelConfig("t-cnn", "cnn", (8, 8, 1), CLASSES,
                             hidden=(4, 8)),
    "tcn": ClientModelConfig("t-tcn", "tcn", (12, 1), CLASSES,
                             hidden=(8, 8), kernel_size=3),
}
FED = FedConfig(num_clients=M, num_neighbors=N, top_k=2, lsh_bits=128)


@functools.lru_cache(maxsize=None)
def _case(kind):
    """One model's stacked clients and personal reference sets, ids of
    every other client (N = M - 1) and ids with padded slots."""
    mcfg = MODELS[kind]
    rs = np.random.RandomState(4)
    params = jax.vmap(functools.partial(init_client_model, mcfg))(
        jax.random.split(jax.random.PRNGKey(0), M))
    data = {"x_ref": jnp.asarray(rs.randn(M, R, *mcfg.input_shape),
                                 jnp.float32),
            "y_ref": jnp.asarray(rs.randint(0, CLASSES, (M, R)), jnp.int32)}
    others = np.array([[j for j in range(M) if j != i] for i in range(M)])
    padded = others.copy()
    # masked-out slots: -1 and -M wrap to a client, -M-1 and M+2 clamp
    holes = {(0, 4): -1, (2, 0): -M, (3, 2): -M - 1, (5, 1): M + 2}
    for (i, s), v in holes.items():
        padded[i, s] = v
    mask = np.ones((M, N), bool)
    for i, s in holes:
        mask[i, s] = False
    apply_fn = functools.partial(apply_client_model, mcfg)
    return apply_fn, params, data, {
        "others": (jnp.asarray(others, jnp.int32), jnp.ones((M, N), bool)),
        "padded": (jnp.asarray(padded, jnp.int32), jnp.asarray(mask))}


def _oracle(apply_fn, params, x_ref, ids):
    """The double vmap over gathered neighbor parameters."""
    nb_params = jax.tree.map(lambda p: p[ids], params)          # (M, N, ...)
    web = jax.vmap(jax.vmap(apply_fn, in_axes=(0, None)))(nb_params, x_ref)
    return jax.vmap(apply_fn)(params, x_ref), web


@pytest.mark.parametrize("ids_case", ["others", "padded"])
@pytest.mark.parametrize("width", [1, N, N + 1, 8])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_mapped_exchange_matches_gathered_double_vmap(kind, width,
                                                      ids_case):
    apply_fn, params, data, cases = _case(kind)
    ids, mask = cases[ids_case]
    own, web = jax.jit(functools.partial(
        protocol._mapped_ref_logits, apply_fn, width=width))(
        params, data["x_ref"], ids)
    want_own, want_web = jax.jit(functools.partial(_oracle, apply_fn))(
        params, data["x_ref"], ids)
    assert own.shape == (M, R, CLASSES) and web.shape == (M, N, R, CLASSES)
    np.testing.assert_allclose(np.asarray(own), np.asarray(want_own),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(web), np.asarray(want_web),
                               rtol=1e-5, atol=1e-6)

    # the exchange over the mapped logits: the oracle's verdicts
    sel = protocol.SelectResult(ids, mask, jnp.zeros((M,)),
                                jnp.ones((M,), bool))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(protocol, "lane_chunk", lambda *a: width)
        got = jax.jit(lambda p: protocol.exchange_phase(
            apply_fn, FED, p, data, sel))(params)
    want = all_in_one_exchange(want_own, public_ref_logits(want_web),
                               data["y_ref"], mask, FED)
    np.testing.assert_array_equal(np.asarray(got.valid_mask),
                                  np.asarray(want.valid_mask))
    np.testing.assert_array_equal(np.asarray(got.l_ij),
                                  np.asarray(want.l_ij))


def _shapes(mcfg):
    params = jax.eval_shape(
        functools.partial(init_client_model, mcfg), jax.random.PRNGKey(0))
    return [x.shape for x in jax.tree.leaves(params)]


@pytest.mark.parametrize("backend,pairs,model,want", [
    ("cpu", 128 * 13, "mnist", 1),     # XLA-CPU keeps the one-pair map
    ("cpu", 1024 * 11, "aecg", 1),
    ("tpu", 128 * 13, "mnist", 8),     # conv2-fc128-mnist at m128:
    ("tpu", 10 * 10, "mnist", 8),      # ... and m10: 8 x 32 = 256 lanes
    ("tpu", 1024 * 11, "aecg", 8),     # tcn3-w32-aecg: the same width
    ("tpu", 3, "aecg", 3),             # capped at the pairs there are
    ("tpu", 128 * 13, "mlp", 1),       # no conv: nothing to fill
])
def test_exchange_width_rule(backend, pairs, model, want):
    shapes = _shapes({"mnist": mnist_cnn(), "aecg": aecg_tcn(),
                      "mlp": ClientModelConfig("t-mlp", "mlp", (6,), 3,
                                               hidden=(16,))}[model])
    assert protocol.lane_chunk(backend, pairs, shapes) == want


@pytest.mark.parametrize("ref_mode,counted", [("personal", 1),
                                              ("public", 0)])
def test_exchange_counts_its_width_on_the_personal_path(ref_mode,
                                                        counted):
    apply_fn, params, data, cases = _case("tcn")
    ids, mask = cases["others"]
    sel = protocol.SelectResult(ids, mask, jnp.zeros((M,)),
                                jnp.ones((M,), bool))
    fed = FedConfig(num_clients=M, num_neighbors=N, top_k=2, lsh_bits=128,
                    ref_mode=ref_mode)
    before = spans.snapshot()["counters"].get(protocol.EXCHANGE_CHUNK, 0)
    jax.jit(lambda p: protocol.exchange_phase(
        apply_fn, fed, p, data, sel)).lower(params)
    after = spans.snapshot()["counters"].get(protocol.EXCHANGE_CHUNK, 0)
    assert jax.default_backend() == "cpu"
    assert after - before == counted
