"""All-in-one exchange subsystem (DESIGN.md §7).

Bit-exactness contracts:
  * fused exchange kernel vs jnp oracle: losses, valid mask, aggregated
    targets identical in interpret mode (incl. the M-padding path);
  * oracle vs the unfused composition the round used to run
    (distill.cross_entropy -> verify.lsh_verification_mask ->
    distill.aggregate_neighbor_outputs): identical, so the refactored
    round's metrics are unchanged by construction;
  * all_in_one_exchange backends agree and the protocol round is
    exchange-backend-invariant end to end.

Semantics regressions for §3.5 and the two reference regimes:
  upper-half keep count, masked neighbors never passing, the
  all-invalid fallback to local-only loss, and personal-vs-public
  ref_mode equivalence when every client holds the same reference set.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import FedConfig
from repro.core import (all_in_one_exchange, distill, exchange_phase,
                        init_state, make_wpfed_round, select_phase, verify)
from repro.core.exchange import ExchangeResult
from repro.kernels import ref
from repro.kernels.exchange import BM_EXC, fused_exchange


def _inputs(m, n, r, c, seed=0, sel_p=0.7):
    k = jax.random.PRNGKey(seed)
    own = jax.random.normal(k, (m, r, c)) * 3
    nb = jax.random.normal(jax.random.fold_in(k, 1), (m, n, r, c)) * 3
    y = jax.random.randint(jax.random.fold_in(k, 2), (m, r), 0, c)
    sel = jax.random.bernoulli(jax.random.fold_in(k, 3), sel_p, (m, n))
    return own, nb, y, sel


# ---------------------------------------------------------------------------
# kernel vs oracle vs unfused composition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,r,c", [
    (6, 3, 12, 3), (7, 5, 8, 10), (1, 4, 4, 5), (9, 1, 3, 4), (16, 8, 16, 7)])
@pytest.mark.parametrize("lsh_verification", [True, False])
def test_exchange_kernel_matches_oracle(m, n, r, c, lsh_verification):
    """m=7/9/1 exercise the BM_EXC padding path."""
    own, nb, y, sel = _inputs(m, n, r, c, seed=m * n)
    out_k = fused_exchange(own, nb, y, sel,
                           lsh_verification=lsh_verification)
    out_o = ref.all_in_one_exchange_ref(own, nb, y, sel,
                                        lsh_verification=lsh_verification)
    for a, b, name in zip(out_k, out_o,
                          ("l_ij", "valid", "target", "has_target")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.all(a == b)), name


@pytest.mark.parametrize("m,n,r,c", [(6, 3, 12, 3), (7, 5, 8, 10)])
def test_exchange_oracle_matches_unfused_composition(m, n, r, c):
    """The oracle is bit-identical to the three scattered calls the
    round ran before the fusion (acceptance: round metrics unchanged).
    Both sides are compiled programs, as in the jitted round."""
    own, nb, y, sel = _inputs(m, n, r, c, seed=m + n)

    @jax.jit
    def legacy(own, nb, y, sel):
        l_legacy = jax.vmap(lambda yl, yy: jax.vmap(
            lambda l: distill.cross_entropy(l, yy))(yl))(nb, y)
        v_legacy = jax.vmap(verify.lsh_verification_mask)(own, nb, sel)
        return (l_legacy, v_legacy,
                *jax.vmap(distill.aggregate_neighbor_outputs)(nb, v_legacy))

    l_legacy, v_legacy, t_legacy, h_legacy = legacy(own, nb, y, sel)
    l_o, v_o, t_o, h_o = ref.all_in_one_exchange_ref(own, nb, y, sel)
    assert bool(jnp.all(l_legacy == l_o))
    assert bool(jnp.all(v_legacy == v_o))
    assert bool(jnp.all(t_legacy == t_o))
    assert bool(jnp.all(h_legacy == h_o))


# ---------------------------------------------------------------------------
# §3.5 semantics regressions (both backends)
# ---------------------------------------------------------------------------
def _fed(m=6, **kw):
    base = dict(num_clients=m, num_neighbors=4, top_k=2, lsh_bits=128)
    base.update(kw)
    return FedConfig(**base)


@pytest.mark.parametrize("backend", ["kernel", "oracle"])
def test_exchange_upper_half_keep_count(backend):
    """ceil(n_valid / 2) of the selected neighbors pass, per client."""
    own, nb, y, sel = _inputs(8, 5, 6, 4, seed=11, sel_p=0.6)
    res = all_in_one_exchange(own, nb, y, sel, _fed(8), backend=backend)
    n_valid = np.asarray(jnp.sum(sel, axis=1))
    kept = np.asarray(jnp.sum(res.valid_mask, axis=1))
    assert (kept == (n_valid + 1) // 2).all()


@pytest.mark.parametrize("backend", ["kernel", "oracle"])
def test_exchange_masked_neighbors_never_pass(backend):
    own, nb, y, sel = _inputs(8, 5, 6, 4, seed=13, sel_p=0.4)
    res = all_in_one_exchange(own, nb, y, sel, _fed(8), backend=backend)
    assert not bool(jnp.any(res.valid_mask & ~sel))


@pytest.mark.parametrize("backend", ["kernel", "oracle"])
def test_exchange_all_invalid_falls_back_to_local_only(backend):
    """No selected neighbors -> zero target, has_target False, and the
    combined loss reduces to the local CE term (Alg. 1's fallback)."""
    own, nb, y, _ = _inputs(5, 3, 4, 3, seed=17)
    sel = jnp.zeros((5, 3), bool)
    res = all_in_one_exchange(own, nb, y, sel, _fed(5), backend=backend)
    assert not bool(jnp.any(res.valid_mask))
    assert not bool(jnp.any(res.has_target))
    assert bool(jnp.all(res.target_ref == 0.0))
    # distill.combined_loss zeroes the ref term when has_target is False
    apply_fn = lambda p, x: x @ p
    p = jnp.eye(3)
    batch = {"x": own[0, :, :3], "y": y[0, :4] % 3}
    _, (_, l_ref) = distill.combined_loss(
        apply_fn, p, batch, own[0], res.target_ref[0],
        res.has_target[0], alpha=0.5)
    assert float(l_ref) == 0.0


@pytest.mark.parametrize("backend", ["kernel", "oracle"])
def test_exchange_verification_off_passes_all_selected(backend):
    own, nb, y, sel = _inputs(6, 4, 5, 3, seed=19, sel_p=0.5)
    fed = _fed(6, lsh_verification=False)
    res = all_in_one_exchange(own, nb, y, sel, fed, backend=backend)
    assert bool(jnp.all(res.valid_mask == sel))


# ---------------------------------------------------------------------------
# all_in_one_exchange entry point
# ---------------------------------------------------------------------------
def test_exchange_backends_agree_via_entry_point():
    own, nb, y, sel = _inputs(10, 4, 6, 5, seed=23)
    fed = _fed(10)
    res_k = all_in_one_exchange(own, nb, y, sel, fed, backend="kernel")
    res_o = all_in_one_exchange(own, nb, y, sel, fed, backend="oracle")
    for a, b, name in zip(res_k, res_o, ExchangeResult._fields):
        assert bool(jnp.all(a == b)), name


def test_exchange_rejects_unknown_backend():
    own, nb, y, sel = _inputs(4, 2, 3, 3)
    with pytest.raises(ValueError):
        all_in_one_exchange(own, nb, y, sel,
                            _fed(4, exchange_backend="cuda"))


def test_exchange_degenerate_no_neighbors():
    """M=1 federation: N=0 — no kernel launch, zeros fallback."""
    own = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 3))
    nb = jnp.zeros((1, 0, 4, 3))
    res = all_in_one_exchange(own, nb, jnp.zeros((1, 4), jnp.int32),
                              jnp.zeros((1, 0), bool), _fed(1))
    assert res.l_ij.shape == (1, 0) and res.valid_mask.shape == (1, 0)
    assert res.target_ref.shape == (1, 4, 3)
    assert not bool(res.has_target[0])


# ---------------------------------------------------------------------------
# protocol integration: backend invariance, phases, metrics, ref modes
# ---------------------------------------------------------------------------
def test_round_exchange_backend_invariant(tiny_fed):
    f = tiny_fed
    out = {}
    for backend in ("oracle", "kernel"):
        fed = dataclasses.replace(f["fed"], exchange_backend=backend)
        state = init_state(f["apply_fn"], f["init_fn"], f["opt"], fed,
                           jax.random.PRNGKey(0))
        round_fn = jax.jit(make_wpfed_round(f["apply_fn"], f["opt"], fed))
        s1, m1 = round_fn(state, f["data"])
        s2, m2 = round_fn(s1, f["data"])
        out[backend] = (s2, m2)
    s_o, m_o = out["oracle"]
    s_k, m_k = out["kernel"]
    assert bool(jnp.all(s_o.codes == s_k.codes))
    assert bool(jnp.all(s_o.rankings == s_k.rankings))
    assert bool(jnp.all(m_o["valid_mask"] == m_k["valid_mask"]))
    np.testing.assert_array_equal(np.asarray(m_o["mean_neighbor_loss"]),
                                  np.asarray(m_k["mean_neighbor_loss"]))


def test_round_metrics_match_phase_composition(tiny_fed):
    """round_fn is exactly select -> exchange -> update -> announce; the
    (fixed) mean_neighbor_loss averages over SELECTED slots only."""
    f = tiny_fed
    state = init_state(f["apply_fn"], f["init_fn"], f["opt"], f["fed"],
                       jax.random.PRNGKey(3))
    round_fn = jax.jit(make_wpfed_round(f["apply_fn"], f["opt"], f["fed"]))
    _, metrics = round_fn(state, f["data"])

    _, rng_sel, _ = jax.random.split(state.rng, 3)
    sel = select_phase(state, f["fed"], rng=rng_sel)
    exch = exchange_phase(f["apply_fn"], f["fed"], state.params,
                          f["data"], sel)
    n_sel = float(jnp.sum(sel.sel_mask))
    expect = float(jnp.sum(jnp.where(sel.sel_mask, exch.l_ij, 0.0))
                   / max(n_sel, 1.0))
    assert np.isclose(float(metrics["mean_neighbor_loss"]), expect,
                      rtol=0, atol=0)
    assert bool(jnp.all(metrics["neighbor_ids"] == sel.ids))
    assert bool(jnp.all(metrics["valid_mask"] == exch.valid_mask))


def test_mean_neighbor_loss_ignores_unselected_slots():
    """Regression for the biased metric: zeros in unselected slots must
    not dilute the average (old code divided by M*N, not the count)."""
    own, nb, y, _ = _inputs(4, 3, 5, 3, seed=29)
    sel = jnp.array([[True, False, False]] * 4)
    res = all_in_one_exchange(own, nb, y, sel, _fed(4), backend="oracle")
    biased = float(jnp.mean(jnp.where(sel, res.l_ij, 0.0)))
    fixed = float(jnp.sum(jnp.where(sel, res.l_ij, 0.0))
                  / jnp.sum(sel.astype(jnp.float32)))
    assert np.isclose(fixed, float(jnp.mean(res.l_ij[:, 0])))
    assert fixed > biased          # losses are positive; bias was downward


def test_ref_mode_public_equals_personal_on_identical_refs(tiny_fed):
    """The abstract's public-reference regime: when every client already
    holds the same reference set, the M-forward public exchange must
    reproduce the M*N-forward personal one."""
    f = tiny_fed
    data = dict(f["data"])
    data["x_ref"] = jnp.broadcast_to(data["x_ref"][:1],
                                     data["x_ref"].shape)
    data["y_ref"] = jnp.broadcast_to(data["y_ref"][:1],
                                     data["y_ref"].shape)
    out = {}
    for mode in ("personal", "public"):
        fed = dataclasses.replace(f["fed"], ref_mode=mode)
        state = init_state(f["apply_fn"], f["init_fn"], f["opt"], fed,
                           jax.random.PRNGKey(1))
        round_fn = jax.jit(make_wpfed_round(f["apply_fn"], f["opt"], fed))
        s1, m1 = round_fn(state, data)
        s2, m2 = round_fn(s1, data)
        out[mode] = (s2, m2)
    s_p, m_p = out["personal"]
    s_u, m_u = out["public"]
    assert bool(jnp.all(m_p["neighbor_ids"] == m_u["neighbor_ids"]))
    assert bool(jnp.all(m_p["valid_mask"] == m_u["valid_mask"]))
    np.testing.assert_allclose(np.asarray(m_p["mean_neighbor_loss"]),
                               np.asarray(m_u["mean_neighbor_loss"]),
                               rtol=1e-6)
    leaves_p = jax.tree.leaves(s_p.params)
    leaves_u = jax.tree.leaves(s_u.params)
    for a, b in zip(leaves_p, leaves_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_ref_mode_rejects_unknown(tiny_fed):
    f = tiny_fed
    fed = dataclasses.replace(f["fed"], ref_mode="shared")
    state = init_state(f["apply_fn"], f["init_fn"], f["opt"], fed,
                       jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        make_wpfed_round(f["apply_fn"], f["opt"], fed)(state, f["data"])


# ---------------------------------------------------------------------------
# launcher wiring
# ---------------------------------------------------------------------------
def test_dryrun_threads_clients_and_ref_mode(monkeypatch, tmp_path):
    """Regression: `--dryrun` used to silently ignore `--clients`."""
    from repro.launch import fed as fed_launch
    # main() places the compile cache; with the variable set it leaves
    # this process's JAX config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = {}

    def fake_dryrun(num_clients=256, arch="phi3-medium-14b",
                    backend="kernel", ref_mode="personal", tiling="auto",
                    reselect_every=1, attack="none", attack_frac=0.5,
                    attack_start=-1):
        calls.update(num_clients=num_clients, backend=backend,
                     ref_mode=ref_mode, tiling=tiling,
                     reselect_every=reselect_every,
                     attack=attack, attack_frac=attack_frac,
                     attack_start=attack_start)

    monkeypatch.setattr(fed_launch, "dryrun_fed_round", fake_dryrun)
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=512")
    fed_launch.main(["--dryrun", "--clients", "32", "--ref-mode", "public"])
    assert calls == {"num_clients": 32, "backend": "kernel",
                     "ref_mode": "public", "tiling": "auto",
                     "reselect_every": 1,
                     "attack": "none", "attack_frac": 0.5,
                     "attack_start": -1}
    fed_launch.main(["--dryrun", "--backend", "oracle",
                     "--tiling", "tiled",
                     "--schedule", "gossip", "--reselect-every", "4",
                     "--attack", "poison", "--attack-frac", "0.25",
                     "--attack-start", "5"])
    assert calls == {"num_clients": 256, "backend": "oracle",
                     "ref_mode": "personal", "tiling": "tiled",
                     "reselect_every": 4,
                     "attack": "poison", "attack_frac": 0.25,
                     "attack_start": 5}
