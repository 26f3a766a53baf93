"""Service-layer tests (DESIGN.md §13): churn invariants, staleness,
heterogeneous gossip budgets, kill/resume bit-exactness, ledger
persistence, and the personalized serving front."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_state
from repro.core.chain import Blockchain, load_chain, save_chain
from repro.core.neighbor import select_partners
from repro.core.protocol import select_phase, update_phase
from repro.service import (ChurnEvent, PersonalizedServer, ServiceConfig,
                           apply_events, init_service_state, join, leave,
                           parse_events, participation_mask, resume_service,
                           run_service, service_program, staleness_discount)
from repro.service.membership import validate_events


@pytest.fixture(scope="module")
def svc_state(tiny_fed):
    svc = ServiceConfig(reselect_every=3, keep_last_k=2)
    state = init_service_state(
        init_state(tiny_fed["apply_fn"], tiny_fed["init_fn"],
                   tiny_fed["opt"], tiny_fed["fed"],
                   jax.random.PRNGKey(0)), svc)
    return {"svc": svc, "state": state, **tiny_fed}


# ---------------------------------------------------------------------------
# churn invariants: the masks through selection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["oracle", "kernel"])
def test_leaver_never_in_any_top_n(tiny_fed, backend):
    """A departed client is excluded from EVERY peer's top-N, whatever
    backend computes the selection."""
    fed = tiny_fed["fed"]
    m = fed.num_clients
    rs = np.random.RandomState(3)
    codes = jnp.asarray(
        rs.randint(0, 2**32, (m, fed.lsh_bits // 32), dtype=np.uint32))
    scores = jnp.asarray(rs.rand(m).astype(np.float32)) + 0.5
    active = jnp.ones((m,), bool).at[4].set(False)
    ids, mask = select_partners(codes, scores, fed, active=active,
                                backend=backend)
    chosen = np.asarray(ids)[np.asarray(mask)]
    assert 4 not in chosen
    # every ACTIVE row still fills its top-N from the remaining cohort
    sel_count = np.asarray(mask).sum(axis=1)
    n = min(fed.num_neighbors, m - 1)
    for i in range(m):
        if i != 4:
            assert sel_count[i] == min(n, m - 2)


def test_active_mask_requires_use_rank(tiny_fed):
    import dataclasses
    fed = dataclasses.replace(tiny_fed["fed"], use_rank=False)
    m = fed.num_clients
    codes = jnp.zeros((m, fed.lsh_bits // 32), jnp.uint32)
    with pytest.raises(ValueError, match="use_rank"):
        select_partners(codes, jnp.ones((m,)), fed,
                        active=jnp.ones((m,), bool))


def test_stale_joiner_selectable_leaver_not(svc_state):
    """The join/leave asymmetry: a re-joined client with code_age > 0
    keeps a FINITE (discounted) weight — with top-N wide enough to
    admit every finite candidate it appears in peers' selections —
    while a departed client's -inf weight keeps it out even then."""
    import dataclasses
    fed = dataclasses.replace(svc_state["fed"], num_neighbors=5)
    state = svc_state["state"]
    # client 5 rejoined two periods stale; client 3 departed
    st = join(leave(state, 5), 5)._replace(
        code_age=state.code_age.at[5].set(2),
        active=state.active.at[3].set(False))
    scale = staleness_discount(st.code_age,
                               svc_state["svc"].staleness_lambda)
    sel = select_phase(st.fed, fed, active=st.active, score_scale=scale)
    chosen = np.asarray(sel.ids)[np.asarray(sel.sel_mask)]
    assert 5 in chosen
    assert 3 not in chosen
    # active rows fill M-2 valid slots (everyone but self and the leaver)
    counts = np.asarray(sel.sel_mask).sum(axis=1)
    for i in range(6):
        if i != 3:
            assert counts[i] == 4


def test_all_but_one_departed_degrades_not_crashes(svc_state):
    """Two survivors -> each selects exactly the other; ONE survivor ->
    zero valid slots, and a full compiled period still runs (the
    exchange's has_target=False path)."""
    fed, svc = svc_state["fed"], svc_state["svc"]
    program = service_program(svc_state["apply_fn"], svc_state["opt"],
                              fed, svc)
    m = fed.num_clients
    two = svc_state["state"]._replace(
        active=jnp.zeros((m,), bool).at[0].set(True).at[2].set(True))
    new_state, sel, _ = jax.jit(program.global_round)(
        two, svc_state["data"])
    ids, mask = np.asarray(sel.ids), np.asarray(sel.sel_mask)
    assert mask[0].sum() == 1 and ids[0][mask[0]][0] == 2
    assert mask[2].sum() == 1 and ids[2][mask[2]][0] == 0
    jax.block_until_ready(new_state)

    from repro.core.rounds import make_segment_fn
    one = svc_state["state"]._replace(
        active=jnp.zeros((m,), bool).at[3].set(True))
    seg = jax.jit(make_segment_fn(program, svc.reselect_every))
    final, metrics = seg(one, svc_state["data"])
    jax.block_until_ready(metrics)
    sel2 = jax.jit(program.global_round)(one, svc_state["data"])[1]
    # the sole survivor has nobody valid to talk to (inactive rows
    # still compute a selection, but they are masked out of updates)
    assert np.asarray(sel2.sel_mask)[3].sum() == 0


def test_leave_freezes_update_and_announce(svc_state):
    """After a leave, the departed client's params, codes, rankings and
    commitments come back bitwise unchanged from a global round, and
    its code_age increments."""
    program = service_program(svc_state["apply_fn"], svc_state["opt"],
                              svc_state["fed"], svc_state["svc"])
    st = leave(svc_state["state"], 1)
    new_state, _, _ = jax.jit(program.global_round)(st, svc_state["data"])
    for old, new in zip(jax.tree.leaves(st.fed.params),
                        jax.tree.leaves(new_state.fed.params)):
        assert np.array_equal(np.asarray(old[1]), np.asarray(new[1]))
        # a participant's params DID move
        assert not np.array_equal(np.asarray(old[0]), np.asarray(new[0]))
    assert np.array_equal(np.asarray(st.fed.codes[1]),
                          np.asarray(new_state.fed.codes[1]))
    assert np.array_equal(np.asarray(st.fed.rankings[1]),
                          np.asarray(new_state.fed.rankings[1]))
    assert int(new_state.code_age[1]) == 1
    assert int(new_state.code_age[0]) == 0


@pytest.mark.parametrize("kind", ["global", "gossip"])
def test_full_membership_service_round_equals_sync_round(svc_state, kind):
    """Every client active, code_age 0 and full gossip budgets: the
    service round is the sync round. Its FedState and the metrics both
    programs report are bitwise equal to `wpfed_program`'s, in the
    global round and in a gossip epoch."""
    from repro.core.protocol import wpfed_program
    args = (svc_state["apply_fn"], svc_state["opt"], svc_state["fed"])
    sync = wpfed_program(*args)
    service = service_program(*args, svc_state["svc"])
    data = svc_state["data"]
    # one sync round first, so the compared round ranks real evidence
    warm = jax.jit(sync.global_round)(svc_state["state"].fed, data)[0]
    st = init_service_state(warm, svc_state["svc"])
    sync_st, sync_sel, sync_m = jax.jit(sync.global_round)(st.fed, data)
    svc_st, svc_sel, svc_m = jax.jit(service.global_round)(st, data)
    if kind == "gossip":
        sync_st, _, sync_m = jax.jit(sync.gossip_round)(
            sync_st, data, sync_sel)
        svc_st, _, svc_m = jax.jit(service.gossip_round)(
            svc_st, data, svc_sel)
    for a, b in zip(jax.tree.leaves(sync_st), jax.tree.leaves(svc_st.fed)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert set(sync_m) <= set(svc_m)
    for k in sync_m:
        assert np.array_equal(np.asarray(sync_m[k]), np.asarray(svc_m[k])), k


def test_sync_and_service_ledger_entries_agree(svc_state):
    """One encoder for both ledgers: for one state, the sync
    publisher's block entries are the transport's with "sum" removed,
    each "sum" is the checksum of the rest, and a client outside the
    announcing mask is left out of both announcements and reveals."""
    from repro.launch.fed import chain_publisher
    from repro.service.transport import (BulletinTransport,
                                         announcement_checksum)
    program = service_program(svc_state["apply_fn"], svc_state["opt"],
                              svc_state["fed"], svc_state["svc"])
    st, _, _ = jax.jit(program.global_round)(svc_state["state"],
                                             svc_state["data"])
    m = svc_state["fed"].num_clients
    chain = Blockchain()
    chain_publisher(chain, m)(0, st.fed)
    block = chain.blocks[-1].payload
    ann, reveals, failed, delayed = BulletinTransport(Blockchain()).collect(
        0, np.ones(m, bool), st)
    assert not failed.any() and not delayed.any()
    assert {str(i): {k: v for k, v in e.items() if k != "sum"}
            for i, e in ann.items()} == block["announcements"]
    assert {str(i): r for i, r in reveals.items()} == block["reveals"]
    for e in ann.values():
        assert e["sum"] == announcement_checksum(e)
    some = np.ones(m, bool)
    some[2] = False
    ann2, reveals2, _, _ = BulletinTransport(Blockchain()).collect(
        0, some, st)
    assert sorted(ann2) == sorted(reveals2) == [i for i in range(m)
                                                if i != 2]
    assert all(ann2[i] == ann[i] for i in ann2)


# ---------------------------------------------------------------------------
# membership mechanics
# ---------------------------------------------------------------------------
def test_participation_mask_heterogeneous_g(svc_state):
    state = svc_state["state"]._replace(
        gossip_count=jnp.asarray([1, 2, 3, 3, 3, 3], jnp.int32),
        active=jnp.ones((6,), bool).at[5].set(False))
    # epoch 0 (first gossip epoch): G_i=1 already exhausted
    assert np.asarray(participation_mask(state, 0)).tolist() == \
        [False, True, True, True, True, False]
    assert np.asarray(participation_mask(state, 1)).tolist() == \
        [False, False, True, True, True, False]


def test_gossip_budget_freezes_mid_period(svc_state):
    """G_i = 1: client trains in the global round, then freezes for the
    period's gossip epochs while others keep moving."""
    fed, svc = svc_state["fed"], svc_state["svc"]
    program = service_program(svc_state["apply_fn"], svc_state["opt"],
                              fed, svc)
    st = svc_state["state"]._replace(
        gossip_count=jnp.asarray([1, 3, 3, 3, 3, 3], jnp.int32))
    g_round = jax.jit(program.global_round)
    after_global, sel, _ = g_round(st, svc_state["data"])
    after_gossip, _, _ = jax.jit(program.gossip_round)(
        after_global, svc_state["data"], sel)
    p0_before = jax.tree.leaves(after_global.fed.params)[0]
    p0_after = jax.tree.leaves(after_gossip.fed.params)[0]
    assert np.array_equal(np.asarray(p0_before[0]), np.asarray(p0_after[0]))
    assert not np.array_equal(np.asarray(p0_before[1]),
                              np.asarray(p0_after[1]))
    # optimizer state frozen too (bit-exact resume depends on it)
    for old, new in zip(jax.tree.leaves(after_global.fed.opt_state),
                        jax.tree.leaves(after_gossip.fed.opt_state)):
        assert np.array_equal(np.asarray(old[0]), np.asarray(new[0]))


def test_churn_event_plumbing():
    assert parse_events("1:leave:4, 2:join:5") == [
        ChurnEvent(1, "leave", 4), ChurnEvent(2, "join", 5)]
    with pytest.raises(ValueError, match="period:kind:client"):
        parse_events("1:leave")
    with pytest.raises(ValueError, match="kind"):
        validate_events([ChurnEvent(0, "lurk", 1)], 6)
    with pytest.raises(ValueError, match="client axis"):
        validate_events([ChurnEvent(0, "join", 6)], 6)


def test_apply_events_idempotent_and_ordered(svc_state):
    state = svc_state["state"]
    events = [ChurnEvent(0, "leave", 2), ChurnEvent(0, "join", 2),
              ChurnEvent(1, "leave", 3), ChurnEvent(0, "leave", 3)]
    s0 = apply_events(state, events, 0)
    # list order within a period: leave(2) then join(2) -> active
    assert bool(s0.active[2]) and not bool(s0.active[3])
    s1 = apply_events(s0, events, 1)
    assert not bool(s1.active[3])


def test_staleness_discount_ordering():
    ages = jnp.asarray([0, 1, 4], jnp.int32)
    d = np.asarray(staleness_discount(ages, 0.5))
    assert d[0] == 1.0 and d[0] > d[1] > d[2] > 0.0
    assert np.allclose(np.asarray(staleness_discount(ages, 0.0)), 1.0)


def test_service_config_validation():
    with pytest.raises(ValueError):
        ServiceConfig(reselect_every=0)
    with pytest.raises(ValueError):
        ServiceConfig(staleness_lambda=-0.1)
    with pytest.raises(ValueError):
        ServiceConfig(keep_last_k=0)


def test_update_phase_none_participate_is_default(svc_state):
    """participate=None must stay bit-exact with the pre-service
    update (the engine pins depend on it)."""
    fed = svc_state["fed"]
    program = service_program(svc_state["apply_fn"], svc_state["opt"],
                              fed, svc_state["svc"])
    st = svc_state["state"]
    sel = select_phase(st.fed, fed, active=st.active,
                       score_scale=staleness_discount(st.code_age, 0.5))
    from repro.core.protocol import exchange_phase
    exch = exchange_phase(svc_state["apply_fn"], fed, st.fed.params,
                          svc_state["data"], sel)
    rng = jax.random.PRNGKey(7)
    all_on = jnp.ones((fed.num_clients,), bool)
    base = update_phase(svc_state["apply_fn"], svc_state["opt"], fed,
                        st.fed.params, st.fed.opt_state,
                        svc_state["data"], exch, rng)
    masked = update_phase(svc_state["apply_fn"], svc_state["opt"], fed,
                          st.fed.params, st.fed.opt_state,
                          svc_state["data"], exch, rng,
                          participate=all_on)
    for a, b in zip(jax.tree.leaves(base[:2]), jax.tree.leaves(masked[:2])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# kill/resume: the acceptance criterion
# ---------------------------------------------------------------------------
def test_kill_resume_bit_exact_with_churn(svc_state, tmp_path):
    """3 churned periods straight through vs killed-after-2 + resumed:
    identical per-round metrics, bitwise-equal final state, payload-
    equal ledgers, and verify_chain across the restart boundary."""
    fed, svc = svc_state["fed"], svc_state["svc"]
    args = (svc_state["apply_fn"], svc_state["opt"], fed, svc)
    events = [ChurnEvent(1, "leave", 4), ChurnEvent(2, "join", 4)]
    taps = []
    s_a, chain_a, hist_a = run_service(
        *args, svc_state["state"], svc_state["data"], periods=3,
        events=events, ckpt_dir=str(tmp_path / "a"),
        metrics_tap=taps.append)
    assert chain_a.verify_chain()
    assert len(hist_a) == 3 * svc.reselect_every
    # the ordered io_callback tap saw every round, in order
    assert len(taps) == len(hist_a)
    assert [t["round"] for t in taps] == [h["round"] for h in hist_a]
    assert all(t["mean_loss"] == h["mean_loss"]
               for t, h in zip(taps, hist_a))
    # churn is visible: period 1 runs with 5/6 active, period 2 with 6/6
    fracs = [h["active_frac"] for h in hist_a]
    assert fracs[0] == 1.0 and abs(fracs[3] - 5 / 6) < 1e-6 \
        and fracs[6] == 1.0

    ckpt_b = str(tmp_path / "b")
    run_service(*args, svc_state["state"], svc_state["data"], periods=2,
                events=events, ckpt_dir=ckpt_b)
    # "kill": fresh template, restore everything from disk
    s_r, chain_r, p0 = resume_service(ckpt_b, svc_state["state"])
    assert p0 == 2
    assert chain_r.verify_chain()
    s_c, chain_c, hist_tail = run_service(
        *args, s_r, svc_state["data"], periods=3, events=events,
        chain=chain_r, ckpt_dir=ckpt_b, start_period=p0)
    assert [h["round"] for h in hist_tail] == \
        [h["round"] for h in hist_a[-svc.reselect_every:]]
    for ha, hb in zip(hist_a[-svc.reselect_every:], hist_tail):
        assert ha == hb  # identical, not approximately equal
    for a, b in zip(jax.tree.leaves(s_a), jax.tree.leaves(s_c)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # hashes differ (wall-clock timestamps); the recorded protocol
    # content must not
    assert [b.payload for b in chain_a.blocks] == \
        [b.payload for b in chain_c.blocks]
    assert chain_c.verify_chain()
    # retention: keep_last_k=2 of 3 periods
    snaps = sorted(f for f in os.listdir(ckpt_b) if f.endswith(".npz"))
    assert snaps == ["step_00000001.npz", "step_00000002.npz"]


def test_resume_refuses_tampered_chain(svc_state, tmp_path):
    fed, svc = svc_state["fed"], svc_state["svc"]
    ckpt = str(tmp_path / "c")
    run_service(svc_state["apply_fn"], svc_state["opt"], fed, svc,
                svc_state["state"], svc_state["data"], periods=1,
                ckpt_dir=ckpt)
    path = os.path.join(ckpt, "chain.json")
    chain = load_chain(path)
    chain.blocks[1].payload["round"] = 999
    with open(path, "w") as fh:
        fh.write(chain.to_json())
    with pytest.raises(ValueError, match="verify_chain"):
        resume_service(ckpt, svc_state["state"])


def test_resume_without_checkpoint_raises(svc_state, tmp_path):
    with pytest.raises(FileNotFoundError):
        resume_service(str(tmp_path / "nope"), svc_state["state"])


def test_chain_json_roundtrip(tmp_path):
    chain = Blockchain()
    chain.publish_round(0, {0: {"lsh": "ab", "commit": "cd"}},
                        reveals={0: [1, 2]})
    chain.publish_round(3, {1: {"lsh": "ef", "commit": "01"}})
    path = str(tmp_path / "chain.json")
    save_chain(path, chain)
    loaded = load_chain(path)
    assert loaded.verify_chain()
    assert [b.hash for b in loaded.blocks] == [b.hash for b in chain.blocks]
    assert loaded.round_block(3).payload == chain.round_block(3).payload
    # tampering after the fact fails verification, not silently passes
    loaded.blocks[1].payload["reveals"]["0"] = [9, 9]
    assert not loaded.verify_chain()


# ---------------------------------------------------------------------------
# the serving front
# ---------------------------------------------------------------------------
def test_personalized_server_matches_direct_apply(svc_state):
    apply_fn = svc_state["apply_fn"]
    params = svc_state["state"].fed.params
    data = svc_state["data"]
    server = PersonalizedServer(apply_fn, params, batch_buckets=(4, 8))
    want = []
    for i, cid in enumerate([3, 0, 5, 3, 1]):  # cross-client batch, dup ids
        server.submit(cid, data["x_test"][cid, i])
        want.append(apply_fn(jax.tree.map(lambda p: p[cid], params),
                             data["x_test"][cid, i][None])[0])
    got = server.flush()
    assert len(got) == 5
    for g, w in zip(got, want):
        assert np.allclose(g, np.asarray(w), atol=1e-5)
    stats = server.throughput()
    assert stats["requests"] == 5
    # 5 requests pad into one bucket-8 batch: padding is accounted for
    assert stats["batches"] == 1 and stats["padded_slots"] == 3


def test_personalized_server_update_params(svc_state):
    apply_fn = svc_state["apply_fn"]
    params = svc_state["state"].fed.params
    data = svc_state["data"]
    server = PersonalizedServer(apply_fn, params)
    server.submit(2, data["x_test"][2, 0])
    before = server.flush()[0]
    server.update_params(jax.tree.map(lambda p: p * 0.5, params))
    server.submit(2, data["x_test"][2, 0])
    after = server.flush()[0]
    assert not np.allclose(before, after)
    with pytest.raises(ValueError, match="client axis"):
        server.update_params(
            jax.tree.map(lambda p: jnp.concatenate([p, p]), params))
    with pytest.raises(ValueError, match="client_id"):
        server.submit(99, data["x_test"][0, 0])
