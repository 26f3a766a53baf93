"""The WPFed round (Algorithm 1), fully jit-able and vmapped over the
client axis, decomposed into four typed phase functions so variant
rounds (async/gossip epochs, public-reference serving) can reuse the
phases instead of forking a monolith (DESIGN.md §7):

  select_phase    §3.6 reveal verification + Eq. 6-8 fused neighbor
                  selection (steps 1-3)
  exchange_phase  the all-in-one reference-set exchange: P2P logit
                  gather + Eq. 3 losses + §3.5 verification + the
                  distillation target, in one kernel-backed pass
                  (steps 4-6a; core.exchange / DESIGN.md §3, §7)
  update_phase    local model updates on the combined objective
                  (Alg. 1 l.19, step 6b)
  announce_phase  new LSH codes, rankings, commitments (step 7)

`wpfed_global_round` (all four phases — one federation iteration for
all M clients) and `wpfed_gossip_round` (exchange + update against the
cached `SelectResult`, DESIGN.md §8) are the one composition of the
phases: `wpfed_program` wraps them as a `core.rounds.RoundProgram`,
and the service's program (`service.driver`) calls them with its
membership masks. Each phase call runs under a
`jax.named_scope` of its short name (`repro.spans.PHASES`), which
names its ops in the compiled program and nothing else. `make_wpfed_round` is the classic sync
adapter over that program. Client models are homogeneous pytrees
stacked on a leading (M,) axis; `launch/fed.py` shards that axis
across the mesh for TPU-scale runs.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.analysis.privacy import sink
from repro.configs.paper_models import FedConfig
from repro.core import distill, lsh, neighbor, ranking, verify
from repro.core.chain import fnv1a_commit
from repro.core.exchange import (ExchangeResult, all_in_one_exchange,
                                 public_ref_logits)
from repro.core.rounds import RoundProgram, program_round
from repro.optim.optimizers import Optimizer, apply_updates

REF_MODES = ("personal", "public")


class FedState(NamedTuple):
    params: Any          # stacked (M, ...)
    opt_state: Any       # stacked (M, ...)
    codes: jnp.ndarray   # (M, W) uint32 — published LSH codes
    rankings: jnp.ndarray     # (M, N) int32 — this round's reveals
    commitments: jnp.ndarray  # (M,) uint32 — commitments to `rankings`
    rng: jnp.ndarray
    round: jnp.ndarray   # scalar int32


class SelectResult(NamedTuple):
    """Output of select_phase: who talks to whom this round."""
    ids: jnp.ndarray            # (M, N) int32 — selected partner ids
    sel_mask: jnp.ndarray       # (M, N) bool — real (non-padded) slots
    scores: jnp.ndarray         # (M,) f32 — Eq. 7 ranking scores
    reporter_mask: jnp.ndarray  # (M,) bool — §3.6 honest reporters


class Announcement(NamedTuple):
    """Output of announce_phase: next round's published state."""
    codes: jnp.ndarray        # (M, W) uint32
    rankings: jnp.ndarray     # (M, N) int32
    commitments: jnp.ndarray  # (M,) uint32


def init_state(apply_fn, init_fn, optimizer: Optimizer, fed: FedConfig,
               key) -> FedState:
    """init_fn(key) -> one client's params."""
    m = fed.num_clients
    keys = jnp.stack(list(jax.random.split(key, m)))
    params = jax.vmap(init_fn)(keys)
    opt_state = jax.vmap(optimizer.init)(params)
    # round-0 codes use the round-0 LSH seed (see announce_phase)
    codes = lsh.stacked_lsh_codes(params, seed=0, bits=fed.lsh_bits,
                                  backend=fed.selection_backend)
    n = min(fed.num_neighbors, m - 1)
    rankings = -jnp.ones((m, n), jnp.int32)
    commitments = fnv1a_commit(rankings, salt=0)
    return FedState(params, opt_state, codes, rankings, commitments,
                    jax.random.fold_in(key, 1), jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def select_phase(state: FedState, fed: FedConfig, *,
                 rng=None, active=None, score_scale=None) -> SelectResult:
    """Steps 1-3: §3.6 reveal verification -> Eq. 7 ranking scores ->
    fused Eq. 6-8 top-N partner selection (DESIGN.md §4). `rng` is
    consumed only by the random-selection ablation (use_lsh=False,
    use_rank=False). The ANN bucket permutation (selection_backend
    "ann", DESIGN.md §11) is seeded from state.round — the same
    per-round discipline as the LSH projection seed in announce_phase,
    so reselection is reproducible, scan-safe, and recomputable by
    every peer from public information.

    The service layer (DESIGN.md §13) threads two optional masks:
    `active` (M,) bool drops departed clients from BOTH sides of the
    round — their stale rankings stop counting as Eq. 7 evidence
    (reporter_mask &= active) and they never enter any peer's top-N
    (neighbor.select_partners forces their score column to -inf);
    `score_scale` (M,) f32 multiplies the Eq. 7 scores — the staleness
    discount for re-joiners whose published codes are periods old.
    Both default to no-ops, keeping the classic sync round bit-exact."""
    m = fed.num_clients
    if fed.rank_verification:
        reporter_mask = verify.verify_rankings_fnv(
            state.rankings, state.commitments)
    else:
        reporter_mask = jnp.ones((m,), bool)
    if active is not None:
        reporter_mask = reporter_mask & active
    scores = ranking.ranking_scores(
        jnp.where(reporter_mask[:, None], state.rankings, -1),
        m, fed.top_k, dedupe=fed.dedupe_rankings)
    if score_scale is not None:
        scores = scores * score_scale
    ids, sel_mask = neighbor.select_partners(
        state.codes, scores, fed,
        rng=rng if not (fed.use_lsh or fed.use_rank) else None,
        seed=state.round, active=active)
    return SelectResult(ids, sel_mask, scores, reporter_mask)


def exchange_phase(apply_fn: Callable, fed: FedConfig, params,
                   data: Dict[str, jnp.ndarray],
                   sel: SelectResult) -> ExchangeResult:
    """Steps 4-6a: evaluate reference sets and run the all-in-one
    exchange (knowledge transfer + quality evaluation + similarity
    verification in one pass — core.exchange, DESIGN.md §7).

    ref_mode="personal": neighbors answer each client's OWN reference
    set, so the logit web needs M*N forwards and the clients' own M,
    run a few (client, neighbor) pairs a step (`personal_ref_logits`)
    with no gather of neighbor parameters up front.

    ref_mode="public": every client evaluates the SHARED reference set
    (row 0 of data["x_ref"] — the abstract's public reference dataset)
    exactly once; the (M, N, R, C) logit web is then a pure gather of
    those M outputs. M forwards instead of M*N and no neighbor-param
    gather, which is what makes large-M federations affordable.
    """
    if fed.ref_mode not in REF_MODES:
        raise ValueError(f"unknown ref_mode: {fed.ref_mode!r} "
                         f"(expected one of {REF_MODES})")
    m = fed.num_clients
    if fed.ref_mode == "public":
        x_shared = data["x_ref"][0]
        own_ref = jax.vmap(apply_fn, in_axes=(0, None))(
            params, x_shared)                           # (M, R, C)
        y_web = public_ref_logits(own_ref[sel.ids])     # (M, N, R, C) gather
        y_ref = jnp.broadcast_to(data["y_ref"][0][None],
                                 (m,) + data["y_ref"].shape[1:])
    else:
        own_ref, web = personal_ref_logits(apply_fn, params,
                                           data["x_ref"], sel.ids)
        y_web = public_ref_logits(web)                  # (M, N, R, C)
        y_ref = data["y_ref"]
    return all_in_one_exchange(own_ref, y_web, y_ref, sel.sel_mask, fed)


def update_phase(apply_fn: Callable, optimizer: Optimizer, fed: FedConfig,
                 params, opt_state, data: Dict[str, jnp.ndarray],
                 exch: ExchangeResult, rng, participate=None):
    """Step 6b: per-client local updates on the combined objective
    (Alg. 1 l.19), distilling toward the exchange's aggregated target.
    Returns (params, opt_state, train_metrics).

    `participate` (M,) bool freezes non-participants: their params AND
    optimizer state come back bitwise unchanged (the service layer's
    departed clients and exhausted per-client gossip budgets,
    DESIGN.md §13). The update still computes for every padded slot —
    static shapes — and is then masked out, so `None` (everyone
    participates) stays bit-exact with the pre-service round."""
    m = fed.num_clients
    upd_keys = jax.vmap(
        lambda i: jax.random.fold_in(rng, i))(jnp.arange(m))
    data_per = {k: data[k] for k in
                ("x_train", "y_train", "x_ref", "y_ref")}
    if fed.ref_mode == "public":        # distill on the shared set
        # broadcast x_ref AND y_ref so the pair stays consistent for
        # any consumer (only x_ref is read by _local_update today)
        for k in ("x_ref", "y_ref"):
            data_per[k] = jnp.broadcast_to(data[k][0][None],
                                           data[k].shape)
    new_params, new_opt, train_metrics = batched_local_update(
        apply_fn, optimizer, fed, params, opt_state, data_per,
        exch.target_ref, exch.has_target, upd_keys)
    if participate is not None:
        def keep(new, old):
            mask = participate.reshape((m,) + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        new_params = jax.tree.map(keep, new_params, params)
        new_opt = jax.tree.map(keep, new_opt, opt_state)
    return new_params, new_opt, train_metrics


def announce_phase(fed: FedConfig, params, sel: SelectResult,
                   exch: ExchangeResult, round_idx) -> Announcement:
    """Step 7: announcements for the next round.

    Codes consumed in round r+1 hash with the shared per-round seed
    r+1: every client projects with the SAME Rademacher matrix
    (distances stay comparable) and the projection rotates each round,
    so a §3.4 attacker cannot precompute a code that stays close to a
    victim across rounds (regression-tested)."""
    codes = lsh.stacked_lsh_codes(params, seed=round_idx + 1,
                                  bits=fed.lsh_bits,
                                  backend=fed.selection_backend)
    rankings = jax.vmap(ranking.make_ranking)(sel.ids, exch.l_ij,
                                              sel.sel_mask)
    # the round's disclosure point: every field crossing to the chain
    # must arrive declassified (repro.analysis.taint proves it)
    return sink("chain-announcement",
                Announcement(codes, rankings,
                             fnv1a_commit(rankings, salt=0)))


# ---------------------------------------------------------------------------
# local updates (shared with core.baselines)
# ---------------------------------------------------------------------------
def _local_update(apply_fn, optimizer, fed: FedConfig, params, opt_state,
                  data_i, target_ref, has_target, rng):
    """`local_steps` minibatch steps on the combined loss for ONE client."""
    n_local = data_i["x_train"].shape[0]
    mb = min(fed.local_batch, n_local)

    def step(carry, key):
        p, s = carry
        idx = jax.random.randint(key, (mb,), 0, n_local)
        batch = {"x": data_i["x_train"][idx], "y": data_i["y_train"][idx]}
        (loss, (l_loc, l_ref)), grads = jax.value_and_grad(
            lambda q: distill.combined_loss(
                apply_fn, q, batch, data_i["x_ref"], target_ref,
                has_target, fed.alpha), has_aux=True)(p)
        updates, s = optimizer.update(grads, s, p)
        return (apply_updates(p, updates), s), (loss, l_loc, l_ref)

    keys = jnp.stack(list(jax.random.split(rng, fed.local_steps)))
    (params, opt_state), (losses, l_locs, l_refs) = jax.lax.scan(
        step, (params, opt_state), keys)
    return params, opt_state, {"loss": losses[-1], "local_loss": l_locs[-1],
                               "ref_loss": l_refs[-1]}


# the lanes a chunk fills in a conv's channel axis, twice the vector
# unit's 128: on a v5e the TCN's width-32 update ran fastest at 8
# clients a step, and the CNN's exchange within 2% of its fastest at 8
# pairs (PERF.md §6)
CHUNK_LANES = 256
# counted each time the update traces: the chunk width it chose
UPDATE_CHUNK = "update.chunk"


def lane_chunk(backend: str, count: int, shapes) -> int:
    """How many of `count` models (clients, or (client, neighbor)
    pairs) one ``lax.map`` step runs at once: the fewest whose convs
    fill `CHUNK_LANES`, capped at `count`.

    `shapes` are one client's parameter leaf shapes. Under ``vmap`` a
    convolution over per-client weights folds the clients into its
    channel axis (``feature_group_count``), so k clients of a conv of
    width w fill k*w lanes where one client pads w to 128. The width
    is the narrowest output channel count of the conv kernels (leaves
    of rank >= 3: window..., in, out). 1 without conv kernels, and on
    the CPU: XLA-CPU runs grouped-conv gradients ~25x slower than
    plain ones, and grouped forwards no faster (measured)."""
    conv = [s for s in shapes if len(s) >= 3]
    if backend == "cpu" or not conv:
        return 1
    return min(count, -(-CHUNK_LANES // min(s[-1] for s in conv)))


def update_chunk(backend: str, m: int, shapes) -> int:
    """How many clients one step of the local update runs at once:
    `lane_chunk` of the M clients, but 1 where the conv kernels hold
    less than half of the parameters. Dense layers batch as separate
    matmuls and gain nothing, and on a v5e chunking the update of a
    client whose parameters sit in a 3136x128 dense layer slowed the
    period program by 11% (PERF.md §6)."""
    conv = [s for s in shapes if len(s) >= 3]
    if 2 * sum(map(math.prod, conv)) < sum(map(math.prod, shapes)):
        return 1
    return lane_chunk(backend, m, shapes)


def _chunked_local_update(apply_fn, optimizer, fed: FedConfig, params,
                          opt_state, data_per, target_ref, has_target,
                          keys, width: int):
    """`_local_update` over the stacked (M, ...) axis, `width` clients
    a step (a remainder chunk where `width` does not divide M); 1 is
    the plain one-client ``lax.map``."""
    def one(args):
        p, s, d, t, h, k = args
        return _local_update(apply_fn, optimizer, fed, p, s, d, t, h, k)

    return jax.lax.map(one, (params, opt_state, data_per, target_ref,
                             has_target, keys),
                       batch_size=width if width > 1 else None)


def batched_local_update(apply_fn, optimizer, fed: FedConfig, params,
                         opt_state, data_per, target_ref, has_target, keys):
    """Per-client local updates over the stacked (M, ...) axis, a
    chunk of clients at a time under ``lax.map``: `update_chunk` picks
    the width from the backend, M and one client's parameter shapes,
    and the trace counts it as `UPDATE_CHUNK`."""
    leaves = jax.tree.leaves(params)
    width = update_chunk(jax.default_backend(), leaves[0].shape[0],
                         [x.shape[1:] for x in leaves])
    spans.count(UPDATE_CHUNK, width)
    return _chunked_local_update(apply_fn, optimizer, fed, params,
                                 opt_state, data_per, target_ref,
                                 has_target, keys, width)


# ---------------------------------------------------------------------------
# the personal exchange's forwards
# ---------------------------------------------------------------------------
# counted each time the personal exchange traces: the chunk width it chose
EXCHANGE_CHUNK = "exchange.chunk"


def _mapped_ref_logits(apply_fn, params, x_ref, ids, width: int):
    """Each client's own logits and its neighbors' on its reference set:
    the (M, N+1) pairs (i, i), (i, ids[i, 0]), ..., (i, ids[i, N-1]),
    `width` pairs a step under ``lax.map`` (a remainder chunk where
    `width` does not divide M*(N+1)); 1 is the plain one-pair map. A
    step indexes the stacked `params` and `x_ref` for its own pairs
    only, the way ``p[ids]`` indexes: a negative id wraps once, then
    an id is clamped into [0, M). Returns own (M, R, C) and the web
    (M, N, R, C)."""
    m, n = ids.shape
    client = jnp.arange(m, dtype=ids.dtype)
    model = jnp.concatenate([client[:, None], ids], axis=1)      # (M, N+1)
    owner = jnp.broadcast_to(client[:, None], model.shape)

    def one(pair):
        i, j = pair
        return apply_fn(jax.tree.map(lambda p: p[j], params), x_ref[i])

    out = jax.lax.map(one, (owner.reshape(-1), model.reshape(-1)),
                      batch_size=width if width > 1 else None)
    out = out.reshape((m, n + 1) + out.shape[1:])
    return out[:, 0], out[:, 1:]


def personal_ref_logits(apply_fn, params, x_ref, ids):
    """`_mapped_ref_logits` at the width `lane_chunk` picks from the
    backend, the M*(N+1) pairs and one client's parameter shapes; the
    trace counts it as `EXCHANGE_CHUNK`. Forwards alone, so unlike the
    update it chunks the dense-heavy CNN too: on a v5e its m128
    exchange took 15.6 ms at 8 pairs a step, 18.5 at 1 and 19.9 at
    one client's 13 (PERF.md §6)."""
    leaves = jax.tree.leaves(params)
    width = lane_chunk(jax.default_backend(),
                       ids.shape[0] * (ids.shape[1] + 1),
                       [x.shape[1:] for x in leaves])
    spans.count(EXCHANGE_CHUNK, width)
    return _mapped_ref_logits(apply_fn, params, x_ref, ids, width)


# ---------------------------------------------------------------------------
# the composed round program
# ---------------------------------------------------------------------------
def _round_metrics(sel: SelectResult, exch: ExchangeResult, train_metrics,
                   round_idx) -> Dict[str, jnp.ndarray]:
    """Per-round metrics shared by the global round and gossip epochs
    (identical structure so a reselection period stacks under scan)."""
    n_sel = jnp.sum(sel.sel_mask.astype(jnp.float32))
    return {
        "round": round_idx,
        "mean_loss": jnp.mean(train_metrics["loss"]),
        "mean_local_loss": jnp.mean(train_metrics["local_loss"]),
        "mean_ref_loss": jnp.mean(train_metrics["ref_loss"]),
        # mean over the SELECTED slots only (padding slots would
        # otherwise dilute the average with zeros)
        "mean_neighbor_loss": (
            jnp.sum(jnp.where(sel.sel_mask, exch.l_ij, 0.0))
            / jnp.maximum(n_sel, 1.0)),
        "valid_neighbor_frac": jnp.mean(
            exch.valid_mask.astype(jnp.float32)),
        "honest_reporter_frac": jnp.mean(
            sel.reporter_mask.astype(jnp.float32)),
        "neighbor_ids": sel.ids,
        "valid_mask": exch.valid_mask,
        "ranking_scores": sel.scores,
    }


def wpfed_global_round(apply_fn: Callable, optimizer: Optimizer,
                       fed: FedConfig, state: FedState,
                       data: Dict[str, jnp.ndarray], *, active=None,
                       score_scale=None
                       ) -> Tuple[FedState, SelectResult, Dict]:
    """Algorithm 1 verbatim: all four phases, each under its named
    scope; returns (state, the round's `SelectResult`, metrics).

    The service's optional membership masks (DESIGN.md §13): `active`
    for `select_phase` and as `update_phase`'s `participate`,
    `score_scale` for `select_phase`. The returned state carries every
    client's fresh announcement; the service keeps only the active
    ones. Without masks this is the classic sync round."""
    rng, rng_sel, rng_upd = jax.random.split(state.rng, 3)

    with jax.named_scope("select"):
        sel = select_phase(state, fed, rng=rng_sel, active=active,
                           score_scale=score_scale)
    with jax.named_scope("exchange"):
        exch = exchange_phase(apply_fn, fed, state.params, data, sel)
    with jax.named_scope("update"):
        params, opt_state, train_metrics = update_phase(
            apply_fn, optimizer, fed, state.params, state.opt_state,
            data, exch, rng_upd, participate=active)
    with jax.named_scope("announce"):
        ann = announce_phase(fed, params, sel, exch, state.round)

    metrics = _round_metrics(sel, exch, train_metrics, state.round)
    new_state = FedState(params, opt_state, ann.codes, ann.rankings,
                         ann.commitments, rng, state.round + 1)
    return new_state, sel, metrics


def wpfed_gossip_round(apply_fn: Callable, optimizer: Optimizer,
                       fed: FedConfig, state: FedState,
                       data: Dict[str, jnp.ndarray], sel: SelectResult,
                       *, participate=None
                       ) -> Tuple[FedState, SelectResult, Dict]:
    """The gossip epoch: exchange + update against the cached `sel`,
    codes / rankings / commitments frozen. `participate` (M,) bool
    freezes the params of the clients it leaves out."""
    rng, rng_upd = jax.random.split(state.rng)
    with jax.named_scope("exchange"):
        exch = exchange_phase(apply_fn, fed, state.params, data, sel)
    with jax.named_scope("update"):
        params, opt_state, train_metrics = update_phase(
            apply_fn, optimizer, fed, state.params, state.opt_state,
            data, exch, rng_upd, participate=participate)
    metrics = _round_metrics(sel, exch, train_metrics, state.round)
    new_state = state._replace(params=params, opt_state=opt_state,
                               rng=rng, round=state.round + 1)
    return new_state, sel, metrics


def wpfed_program(apply_fn: Callable, optimizer: Optimizer,
                  fed: FedConfig) -> RoundProgram:
    """WPFed as a round program (DESIGN.md §8): the global round's
    cache is its `SelectResult`, and the gossip epochs between
    reselections skip announce_phase and the LSH re-code, so a
    reselection period costs one global round plus G-1
    exchange/update epochs."""
    return RoundProgram(
        "wpfed",
        functools.partial(wpfed_global_round, apply_fn, optimizer, fed),
        functools.partial(wpfed_gossip_round, apply_fn, optimizer, fed))


def make_wpfed_round(apply_fn: Callable, optimizer: Optimizer,
                     fed: FedConfig):
    """Classic sync API: round_fn(state, data) -> (state, metrics) —
    the adapter over `wpfed_program`'s global round. `data` is the
    stacked federated dataset dict (see data.federated.stacked)."""
    return program_round(wpfed_program(apply_fn, optimizer, fed))


def evaluate(apply_fn, state: FedState, data, honest_mask=None):
    """Per-client test accuracy; mean over honest clients if mask given.

    One client per step under ``lax.map``, unlike the local update's
    chunks: on a TPU v5e the vmapped form returned wrong accuracies for
    6 of 10 mnist-cnn clients although its logits equalled the
    per-client forward's (PERF.md §6)."""
    acc = jax.lax.map(
        lambda a: distill.accuracy(apply_fn(a[0], a[1]), a[2]),
        (state.params, data["x_test"], data["y_test"]))
    if honest_mask is not None:
        mean = (jnp.sum(acc * honest_mask)
                / jnp.maximum(jnp.sum(honest_mask), 1.0))
    else:
        mean = jnp.mean(acc)
    return {"per_client_acc": acc, "mean_acc": mean}
