"""Continuous federation driver (DESIGN.md §13).

An io_callback / host-loop hybrid over the `core.rounds` engine:

  * INSIDE each reselection period everything is one compiled segment
    (`make_segment_fn` — global round + L-1 gossip epochs under
    lax.scan). Per-round scalar metrics can additionally stream to the
    host mid-segment through the engine's ordered-io_callback metrics
    tap, so a service operator sees rounds as they happen rather than
    once per period.
  * BETWEEN periods the host loop runs: churn events apply
    (membership.apply_events), the period's announcements publish to
    the host `Blockchain`, and the full ServiceState checkpoints
    through `checkpoint.store` (with retention) so a killed service
    resumes bit-exact (`resume_service`).

The service round program runs `core.protocol`'s global round and
gossip epoch under the membership masks:

  global round   §3.6 verification restricted to active reporters,
                 Eq. 8 scores discounted by exp(-lambda * code_age)
                 and forced to -inf for departed clients, updates and
                 announcements applied to active clients only
                 (inactive slots keep frozen codes/rankings/params and
                 age one period).
  gossip epoch   exchange + update against the cached SelectResult,
                 with the per-client heterogeneous gossip budget G_i:
                 client i trains only in the first G_i - 1 gossip
                 epochs of the period.

Unlike `run_rounds`, every period has the same (full) length — a
service has no final-rounds tail — so exactly ONE segment compiles per
run and the round axis is unbounded.

Faults and degraded rounds (DESIGN.md §15): every ledger interaction
routes through `service.transport.BulletinTransport` — checksummed
announcements, bounded-retry publish/fetch, and (when a
`core.faults.FaultPlan` is supplied) deterministic fault injection.
Stragglers mask out of the segment through the SAME churn masking that
join/leave uses; failed deliveries revert to last-known-good codes
after the segment (`membership.merge_delivery`); per-period fault
counters stream through the existing io_callback metric channel and
land on the period's history entries.
"""
from __future__ import annotations

import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.analysis.privacy import sink
from repro.checkpoint import store
from repro.configs.paper_models import FedConfig
from repro.core.chain import Blockchain, save_chain
from repro.core.faults import FaultPlan, fault_scalars
from repro.core.protocol import wpfed_global_round, wpfed_gossip_round
from repro.core.rounds import (RoundProgram, extract_history,
                               make_segment_fn, run_period_program)
from repro.service.membership import (ChurnEvent, ServiceConfig,
                                      ServiceState, apply_events,
                                      mask_stragglers, merge_delivery,
                                      participation_mask,
                                      staleness_discount, validate_events)
from repro.service.transport import (CHAIN_FILE, BulletinTransport,
                                     recover_chain, rollback_view,
                                     write_fork_view)


class CrashInjected(RuntimeError):
    """A FaultPlan-scheduled crash-restart fired: the driver dies after
    the period's segment but BEFORE any durable effect (publish /
    checkpoint), exactly where a real process kill hurts most. The
    chaos soak catches this, resumes from the last checkpoint, and
    asserts bitwise equivalence with the uninterrupted run."""

    def __init__(self, period: int):
        super().__init__(
            f"fault-injected crash at period {period} (resume from the "
            f"last checkpoint to continue)")
        self.period = period


# ---------------------------------------------------------------------------
# the service round program
# ---------------------------------------------------------------------------
def _service_metrics(metrics, state: ServiceState,
                     participate) -> Dict[str, jnp.ndarray]:
    """The engine's per-round metrics plus the membership telemetry.
    Identical structure in the global round and every gossip epoch so
    a period stacks under lax.scan."""
    metrics["active_frac"] = jnp.mean(state.active.astype(jnp.float32))
    metrics["participation_frac"] = jnp.mean(
        participate.astype(jnp.float32))
    metrics["mean_code_age"] = jnp.mean(
        state.code_age.astype(jnp.float32))
    return metrics


def service_program(apply_fn: Callable, optimizer, fed: FedConfig,
                    svc: ServiceConfig) -> RoundProgram:
    """WPFed as a churn-tolerant service program over ServiceState:
    `core.protocol`'s global round and gossip epoch under the
    membership masks.

    The decision here is churn-as-masking (DESIGN.md §13): departed
    clients still occupy their padded slot and their (frozen) params
    still evaluate inside exchanges that never read them — the price of
    one static shape per segment. What the masks guarantee:

      * a departed client's Eq. 8 weight is -inf, so it never enters
        any peer's top-N (and its stale rankings stop counting as
        Eq. 7 evidence);
      * a stale re-joiner is selectable, at a score discounted by
        exp(-staleness_lambda * code_age);
      * only participants' params / optimizer state advance;
      * only active clients announce — everyone else's codes,
        rankings, commitments and code_age carry over frozen.
    """
    if not fed.use_rank:
        raise ValueError(
            "the service requires use_rank=True: departed clients are "
            "excluded through the Eq. 8 score column (membership.py)")

    def global_round(state: ServiceState, data
                     ) -> Tuple[ServiceState, Any, Dict]:
        st, a = state.fed, state.active
        new_fed, sel, metrics = wpfed_global_round(
            apply_fn, optimizer, fed, st, data, active=a,
            score_scale=staleness_discount(state.code_age,
                                           svc.staleness_lambda))
        # these merged fields are what transport.collect reads onto the
        # host ledger and what checkpoints as chain.json — the service's
        # disclosure point (repro.analysis.taint verifies it)
        codes, rankings, commitments = sink("ledger-publish", (
            jnp.where(a[:, None], new_fed.codes, st.codes),
            jnp.where(a[:, None], new_fed.rankings, st.rankings),
            jnp.where(a, new_fed.commitments, st.commitments)))
        new_fed = new_fed._replace(codes=codes, rankings=rankings,
                                   commitments=commitments)
        new_state = ServiceState(
            new_fed, a, jnp.where(a, 0, state.code_age + 1),
            state.gossip_count, jnp.asarray(st.round, jnp.int32))
        return new_state, sel, _service_metrics(metrics, state, a)

    def gossip_round(state: ServiceState, data, sel
                     ) -> Tuple[ServiceState, Any, Dict]:
        # 0-based gossip epoch within the period (round already
        # advanced past the period's global round)
        epoch = state.fed.round - state.period_start - 1
        part = participation_mask(state, epoch)
        new_fed, sel, metrics = wpfed_gossip_round(
            apply_fn, optimizer, fed, state.fed, data, sel,
            participate=part)
        return (state._replace(fed=new_fed), sel,
                _service_metrics(metrics, state, part))

    return RoundProgram("wpfed-service", global_round, gossip_round)


# ---------------------------------------------------------------------------
# durable state
# ---------------------------------------------------------------------------
def checkpoint_service(ckpt_dir: str, period: int, state: ServiceState,
                       chain: Blockchain, *, keep_last_k: int) -> str:
    """One durable snapshot: the full ServiceState pytree as
    step_<period>.npz (retained to the last k) plus the chain head as
    chain.json — everything `resume_service` needs."""
    path = store.save(ckpt_dir, period, state, keep_last_k=keep_last_k)
    save_chain(os.path.join(ckpt_dir, CHAIN_FILE), chain)
    return path


def checkpoint_num_clients(ckpt_dir: str) -> int:  # analysis: host-ok — reads snapshot file metadata, no device values
    """Client-axis size M of the latest snapshot, read from the stored
    active mask WITHOUT a template — lets a serving front rebuild a
    correctly-shaped template before calling resume_service."""
    period = store.latest_step(ckpt_dir)
    if period is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir!r}")
    with np.load(os.path.join(ckpt_dir,
                              f"step_{period:08d}.npz")) as z:
        return int(z["a:active"].shape[0])


def resume_service(ckpt_dir: str, like: ServiceState
                   ) -> Tuple[ServiceState, Blockchain, int]:
    """Restore (state, chain, next_period), crash-safely.

    `like` is a template ServiceState (same configs/shapes as the run
    being resumed — rebuild it with init_service_state).

    Degraded starts this survives: a truncated/corrupt newest snapshot
    falls back (with a warning) to the previous retained one; a
    tampered or missing chain.json falls back to any valid
    chain.fork*.json view, longest-valid-chain wins (transport.
    recover_chain). Trust violations it refuses: NO ledger view
    verifying at all (ValueError, as in PR 8), and a ledger that
    verifies but sits BEHIND the checkpoint's round counter
    (LedgerRollbackError — silent rollback is a fork symptom, not a
    degraded start)."""
    retained = store.steps(ckpt_dir)
    if not retained:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir!r}")
    state, period = None, -1
    for step in reversed(retained):
        try:
            # restore() hands back numpy leaves; put them on device so
            # the resumed state drops into the compiled segment
            # unchanged
            state = jax.tree.map(jnp.asarray,
                                 store.restore(ckpt_dir, step, like))
            period = step
            break
        except Exception as e:
            warnings.warn(
                f"checkpoint step_{step:08d}.npz unreadable ({e}); "
                f"falling back to the previous retained snapshot")
    if state is None:
        raise ValueError(
            f"every retained checkpoint under {ckpt_dir!r} failed to "
            f"load ({len(retained)} tried) — no snapshot to resume from")
    # the checkpoint's round counter: the chain must cover the period
    # that produced this snapshot, else it silently lost history
    min_round = int(state.period_start)  # analysis: host-ok — one scalar pull to cross-check ledger coverage at resume
    chain = recover_chain(ckpt_dir, min_round=min_round)
    return state, chain, period + 1


# ---------------------------------------------------------------------------
# the continuous driver
# ---------------------------------------------------------------------------
def run_service(apply_fn: Callable, optimizer, fed: FedConfig,
                svc: ServiceConfig, state: ServiceState, data, *,
                periods: int, events: Sequence[ChurnEvent] = (),
                chain: Optional[Blockchain] = None,
                ckpt_dir: Optional[str] = None, start_period: int = 0,
                eval_fn: Optional[Callable] = None,
                metrics_tap: Optional[Callable] = None,
                log: Optional[Callable] = None,
                faults: Optional[FaultPlan] = None,
                transport: Optional[BulletinTransport] = None
                ) -> Tuple[ServiceState, Blockchain, List[Dict]]:
    """Drive reselection periods `start_period .. periods-1`.

    Per period: apply churn events -> mask this period's stragglers
    (fault plans only) -> run ONE compiled segment of
    svc.reselect_every rounds -> reconcile announcement delivery and
    publish through the hardened transport (checksums, bounded retry,
    read-back fetch) -> checkpoint (every svc.checkpoint_every periods,
    retaining svc.keep_last_k snapshots). `metrics_tap(scalars_dict)`
    streams per-round scalars from INSIDE the compiled segment (ordered
    io_callback) — under a fault plan each round's dict additionally
    carries the period's fault counters (`core.faults.fault_scalars`).
    The returned history is extracted from the stacked period metrics
    after the host sync, exactly like run_rounds, with the fault
    counters attached to each period's last entry.

    `faults=FaultPlan(...)` turns on deterministic fault injection
    (shorthand for transport=BulletinTransport(chain, plan=faults));
    pass `transport=` directly to control retry policy or sleeping. A
    plan-scheduled crash period raises CrashInjected after the segment,
    before publish/checkpoint — except at `start_period` itself, so a
    resume that lands on the crash period replays it instead of dying
    in a loop.

    Each period is recorded (`repro.spans`) as a `period` span holding
    `period.events`, `period.dispatch` or `period.compile`,
    `period.wait`, the transport's `ledger.*` spans, `period.history`,
    `period.checkpoint` and `period.log`.

    Restart recipe: rebuild (fed, svc, state-template, data, events)
    from the same configuration, then
    `state, chain, p0 = resume_service(ckpt_dir, template)` and call
    run_service again with start_period=p0 — per-round metrics are
    identical to the uninterrupted run (regression-tested, fault plans
    included).
    """
    events = validate_events(events, fed.num_clients)
    chain = chain if chain is not None else Blockchain()
    if transport is None:
        transport = BulletinTransport(chain, plan=faults)
    elif faults is not None and transport.plan is not faults:
        raise ValueError("pass either faults= or a transport= carrying "
                         "its own plan, not both")
    chain = transport.chain
    program = service_program(apply_fn, optimizer, fed, svc)
    length = svc.reselect_every

    # the fault-counter side channel into the compiled segment's metric
    # stream: the host cell is rewritten before each period's segment
    # runs, and the ordered io_callback tap reads it as rounds stream
    fault_cell: Dict[str, float] = {}
    tap = metrics_tap
    if metrics_tap is not None and transport.plan is not None:
        def tap(scalars):
            metrics_tap({**scalars, **fault_cell})
    seg_fn = jax.jit(make_segment_fn(program, length, eval_fn=eval_fn,
                                     metrics_tap=tap))
    history: List[Dict] = []
    for period in range(start_period, periods):
        with spans.span("period", period=period):
            with spans.span("period.events"):
                state = apply_events(state, events, period)
                base_active = state.active
                pf = transport.period_faults(period, fed.num_clients)
                scalars = None
                if pf is not None:
                    announcing = np.asarray(base_active, bool)  # analysis: host-ok — membership mask pull for host-side fault bookkeeping
                    spans.count(spans.HOST_PULLS)
                    scalars = fault_scalars(pf, announcing)
                    fault_cell.clear()
                    fault_cell.update(scalars)
                    stragglers = transport.straggler_mask(period,
                                                          announcing)
                    if stragglers.any():
                        # degraded round: proceed on partial
                        # announcements by the same masking churn uses
                        # (bit-identical to those clients leaving for
                        # one period)
                        state = mask_stragglers(state, stragglers)
                    pre = (state.fed.codes, state.fed.rankings,
                           state.fed.commitments, state.code_age)
            seg_active = state.active
            state, metrics, dt = run_period_program(seg_fn, state, data)
            if pf is not None and pf.crash and period != start_period:
                raise CrashInjected(period)
            r0 = period * length
            if pf is not None:
                state = state._replace(active=base_active)
            # the transport pulls the announcing mask with the
            # announcements
            ann, reveals, failed, delayed = transport.collect(
                period, seg_active, state)
            if pf is not None and (failed.any() or delayed.any()):
                with spans.span("period.events"):
                    state = merge_delivery(state, *pre, failed=failed,
                                           delayed=delayed)
            transport.publish(period, r0, ann, reveals)
            transport.fetch(period, r0)  # read-back verification
            with spans.span("period.history"):
                entries = extract_history(metrics, r0, length)
            if scalars is not None:
                entries[-1].update(scalars)
            history.extend(entries)
            if ckpt_dir is not None and \
                    (period + 1 - start_period) % svc.checkpoint_every == 0:
                with spans.span("period.checkpoint"):
                    checkpoint_service(ckpt_dir, period, state, chain,
                                       keep_last_k=svc.keep_last_k)
                    if transport.plan is not None and \
                            transport.plan.fork_at == period:
                        # fault injection: a competing rolled-back
                        # ledger view appears next to chain.json —
                        # resume must arbitrate
                        write_fork_view(ckpt_dir, rollback_view(chain, 1))
            if log is not None:
                with spans.span("period.log"):
                    last = history[-1]
                    parts = [f"{k} {last[k]:.4f}"
                             for k in ("acc", "mean_loss") if k in last]
                    degraded = " DEGRADED" if scalars and \
                        scalars.get("degraded_round") else ""
                    log(f"period {period:3d} (rounds {r0}..{r0 + length - 1}) "
                        + " ".join(parts)
                        + f" active {last['active_frac']:.2f}"
                        + f" ({dt:.1f}s){degraded}")
    return state, chain, history
