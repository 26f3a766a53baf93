"""Federated launcher: run the WPFed protocol at laptop scale (paper
reproduction) or lower a round program onto the production mesh with
the client axis sharded over "data" (TPU scale-out — beyond-paper).

Rounds run through the round-program engine (`core.rounds.run_rounds`,
DESIGN.md §8): `--schedule sync` is the paper's per-round protocol,
`--schedule gossip --reselect-every G` runs the global LSH
re-selection every G rounds with cheap gossip epochs in between, and
the host `Blockchain` ledger records one block per reselection.

    PYTHONPATH=src python -m repro.launch.fed --dataset mnist --rounds 10
    PYTHONPATH=src python -m repro.launch.fed --schedule gossip \
        --reselect-every 4 --rounds 12
    PYTHONPATH=src python -m repro.launch.fed --dryrun   # 256-client mesh
"""
from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.paper_models import (FedConfig, PAPER_FED_OPTIMA,
                                        aecg_tcn, mnist_cnn,
                                        recommended_dedupe, seeg_tcn)
from repro.core import (evaluate, init_state, instrument_program,
                        make_segment_fn, resolve_schedule, resolve_threat,
                        run_rounds, wpfed_program)
from repro.core.adversary import THREATS
from repro.core.chain import Blockchain, encode_announcements
from repro.data import DATASETS
from repro.models import apply_client_model, init_client_model
from repro.optim import adam
from repro.service import (ServiceConfig, init_service_state, parse_events,
                           parse_fault_spec, resume_service, run_service)

MODEL_FOR = {"mnist": mnist_cnn, "aecg": aecg_tcn, "seeg": seeg_tcn}


def chain_publisher(chain: Blockchain, num_clients: int):
    """`on_reselect` callback: publish a reselection's announcements
    a_i = {lsh_i, C_i} plus the revealed rankings to the host ledger
    (WPFed §2.2 — codes/rankings/commitments are frozen across the
    period's gossip epochs, so one block per reselection is the
    complete record)."""

    def publish(round_idx: int, state) -> None:  # analysis: host-ok
        # intentional device->host pull, once per reselection period:
        # the ledger records announcements, not device arrays (§8)
        with spans.span("ledger.publish"):
            codes = np.asarray(state.codes)
            rankings = np.asarray(state.rankings)
            spans.count(spans.HOST_PULLS, 2)
            ann, reveals = encode_announcements(codes[:num_clients],
                                                rankings[:num_clients])
            chain.publish_round(round_idx + 1, ann, reveals=reveals)

    return publish


def run_federation(dataset: str = "mnist", rounds: int = 10,
                   num_clients: int = 0, seed: int = 0, fed: FedConfig = None,
                   backend: str = "auto", ref_mode: str = "personal",
                   tiling: str = "auto", schedule: str = "sync",
                   reselect_every: int = 0, attack: str = "none",
                   attack_frac: float = 0.5, attack_start: int = -1,
                   ann_prefix_bits: int = -1, ann_probes: int = -1,
                   log=print):
    """`backend` drives BOTH kernel-backed subsystems (selection and
    exchange — one flag, resolved by repro.core.backends.resolve;
    "ann" applies to selection only and leaves exchange on "auto" —
    DESIGN.md §11), and
    `tiling` both VMEM regimes (resolve_tiling, DESIGN.md §10).
    An explicit `fed` config wins outright: backend/ref_mode/tiling
    apply only to the default-constructed config (asserted, not
    silently dropped). ref_mode="public" also enables the Eq. 7
    duplicate-evidence dedupe (every selector sees the same l_ij for a
    neighbor there — DESIGN.md §7). `schedule`/`reselect_every` resolve
    via core.rounds.resolve_schedule; `attack` resolves via
    core.adversary.resolve_threat and instruments the program in-graph
    (DESIGN.md §9) — evaluation then reports the honest cohort.
    `attack_start=-1` keeps the threat's registry defaults (e.g. the
    §4.8 poison warm-up). Publishes every reselection to a host
    `Blockchain` and verifies the chain before returning
    (state, history).
    """
    if fed is not None and (backend != "auto" or ref_mode != "personal"
                            or tiling != "auto" or ann_prefix_bits >= 0
                            or ann_probes >= 0):
        raise ValueError("pass backend/ref_mode/tiling/ann knobs inside "
                         "the explicit FedConfig, not alongside it")
    sched = resolve_schedule(schedule, reselect_every)
    ds_fn = DATASETS[dataset]
    ds = ds_fn(seed=seed) if num_clients == 0 else \
        ds_fn(num_clients=num_clients, seed=seed)
    n_opt, alpha, gamma = PAPER_FED_OPTIMA[dataset]
    defaults = FedConfig()
    fed = fed or FedConfig(num_clients=ds.num_clients, num_neighbors=n_opt,
                           alpha=alpha, gamma=gamma, rounds=rounds,
                           selection_backend=backend,
                           exchange_backend="auto" if backend == "ann"
                           else backend, ref_mode=ref_mode,
                           selection_tiling=tiling, exchange_tiling=tiling,
                           dedupe_rankings=recommended_dedupe(ref_mode),
                           ann_prefix_bits=ann_prefix_bits
                           if ann_prefix_bits >= 0
                           else defaults.ann_prefix_bits,
                           ann_probes=ann_probes if ann_probes >= 0
                           else defaults.ann_probes)
    mcfg = MODEL_FOR[dataset]()
    apply_fn = functools.partial(apply_client_model, mcfg)
    init_fn = lambda k: init_client_model(mcfg, k)
    opt = adam(fed.lr)
    data = {k: jnp.asarray(v) for k, v in ds.stacked().items()}
    state = init_state(apply_fn, init_fn, opt, fed, jax.random.PRNGKey(seed))
    program = wpfed_program(apply_fn, opt, fed)
    honest_mask = None
    if attack != "none":
        tm = resolve_threat(
            attack, num_clients=fed.num_clients, attacker_frac=attack_frac,
            init_fn=init_fn, key=jax.random.PRNGKey(seed + 31),
            start_round=None if attack_start < 0 else attack_start)
        program = instrument_program(program, tm)
        honest_mask = (~tm.attacker_mask).astype(jnp.float32)
    chain = Blockchain()
    state, history = run_rounds(
        program, state, data, rounds=rounds, schedule=sched,
        eval_fn=lambda st, d: {"acc": evaluate(
            apply_fn, st, d, honest_mask=honest_mask)["mean_acc"]},
        on_reselect=chain_publisher(chain, fed.num_clients), log=log)
    assert chain.verify_chain(), "host ledger integrity violated"
    return state, history


def run_service_federation(dataset: str = "mnist", periods: int = 3,
                           reselect_every: int = 4, num_clients: int = 0,
                           seed: int = 0, churn: str = "",
                           gossip_counts: str = "",
                           staleness_lambda: float = 0.5,
                           checkpoint_every: int = 1, keep_last_k: int = 3,
                           ckpt_dir: str = None, resume: bool = False,
                           faults: str = "", log=print):
    """The continuous-service scenario (DESIGN.md §13): the same
    construction as `run_federation`, driven by `repro.service` instead
    of run_rounds — unbounded reselection periods, churn events between
    them (`churn` = "period:kind:client,..."), per-client gossip
    budgets (`gossip_counts` = comma list of G_i), durable checkpoints
    under `ckpt_dir`, `--resume` picking up a killed service from
    its latest readable snapshot (bit-exact, verified against the
    recovered ledger), and `faults` (a `core.faults.parse_fault_spec`
    string, e.g. "seed=7,drop=0.1,straggle=0.2") running the whole
    service under deterministic fault injection (DESIGN.md §15).
    Evaluation reports the ACTIVE cohort — departed clients' frozen
    models don't dilute the service metric. Returns
    (state, chain, history)."""
    ds_fn = DATASETS[dataset]
    ds = ds_fn(seed=seed) if num_clients == 0 else \
        ds_fn(num_clients=num_clients, seed=seed)
    n_opt, alpha, gamma = PAPER_FED_OPTIMA[dataset]
    fed = FedConfig(num_clients=ds.num_clients, num_neighbors=n_opt,
                    alpha=alpha, gamma=gamma,
                    rounds=periods * reselect_every)
    svc = ServiceConfig(reselect_every=reselect_every,
                        staleness_lambda=staleness_lambda,
                        checkpoint_every=checkpoint_every,
                        keep_last_k=keep_last_k)
    mcfg = MODEL_FOR[dataset]()
    apply_fn = functools.partial(apply_client_model, mcfg)
    init_fn = lambda k: init_client_model(mcfg, k)
    opt = adam(fed.lr)
    data = {k: jnp.asarray(v) for k, v in ds.stacked().items()}
    counts = None
    if gossip_counts:
        counts = [int(c) for c in gossip_counts.split(",")]
    template = init_service_state(
        init_state(apply_fn, init_fn, opt, fed, jax.random.PRNGKey(seed)),
        svc, gossip_counts=counts)
    if resume:
        if not ckpt_dir:
            raise ValueError("--resume needs --ckpt-dir")
        state, chain, start_period = resume_service(ckpt_dir, template)
    else:
        state, chain, start_period = template, Blockchain(), 0
    events = parse_events(churn) if churn else []
    plan = parse_fault_spec(faults) if faults else None
    state, chain, history = run_service(
        apply_fn, opt, fed, svc, state, data, periods=periods,
        events=events, chain=chain, ckpt_dir=ckpt_dir,
        start_period=start_period, faults=plan,
        eval_fn=lambda st, d: {"acc": evaluate(
            apply_fn, st.fed, d,
            honest_mask=st.active.astype(jnp.float32))["mean_acc"]},
        log=log)
    assert chain.verify_chain(), "host ledger integrity violated"
    return state, chain, history


def dryrun_fed_round(num_clients: int = 256, arch: str = "phi3-medium-14b",
                     backend: str = "kernel", ref_mode: str = "personal",
                     tiling: str = "auto", reselect_every: int = 1,
                     attack: str = "none", attack_frac: float = 0.5,
                     attack_start: int = -1):
    """Beyond-paper: lower one WPFed reselection period with
    REDUCED-transformer clients sharded over the production mesh's data
    axis — proves the protocol itself scales out (the paper simulated
    <=40 clients on GPU). Defaults to the kernel backends so the
    lowering exercises the batched LSH + fused selection + fused
    exchange kernels under sharding; ref_mode="public" lowers the
    M-forward shared-reference exchange instead of the M*N personal
    one (DESIGN.md §7). `tiling="tiled"` forces the VMEM-tiled
    streaming kernels (column-tiled selection + R/C-tiled exchange,
    DESIGN.md §10) so their lowering composes with sharding — at the
    dryrun's own lsh_bits=128 / C=1024 shapes "auto" still resolves
    to one-shot (the budget only forces tiled past M ~ 10^4 at
    256-bit codes, or vocab-scale C), which is exactly why the tiled
    path needs the explicit flag here. `reselect_every=G` lowers the
    full segment —
    one global round plus G-1 gossip epochs under lax.scan
    (DESIGN.md §8). `attack` instruments the program with an in-graph
    ThreatModel before lowering (DESIGN.md §9) — e.g. a 256-client
    poisoned segment, with the lax.cond-gated re-init of the attacker
    cohort compiled into the sharded round.

    Must be called in a fresh process with XLA_FLAGS set (see dryrun.py).
    """
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.models.transformer import forward, init_params
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(arch).reduced()
    fed = FedConfig(num_clients=num_clients, num_neighbors=8, top_k=4,
                    local_steps=1, lsh_bits=128, ref_batch=8,
                    selection_backend=backend,
                    exchange_backend="kernel" if backend == "ann"
                    else backend,
                    ref_mode=ref_mode, selection_tiling=tiling,
                    exchange_tiling=tiling,
                    dedupe_rankings=recommended_dedupe(ref_mode))
    mesh = make_production_mesh()

    def apply_fn(params, tokens):
        logits, _ = forward(cfg, params, tokens)
        return logits[:, -1, :]                     # classify-next-token

    init_fn = functools.partial(init_params, cfg, dtype=jnp.bfloat16)
    opt = adam(fed.lr)
    program = wpfed_program(apply_fn, opt, fed)
    if attack != "none":
        # the lowering traces BOTH lax.cond branches, so any
        # attack_start exercises the full attacked graph
        program = instrument_program(program, resolve_threat(
            attack, num_clients=num_clients, attacker_frac=attack_frac,
            init_fn=init_fn, key=jax.random.PRNGKey(1),
            start_round=None if attack_start < 0 else attack_start))
    segment_fn = make_segment_fn(program, reselect_every)

    m, r, s = num_clients, 8, 32
    sds = jax.ShapeDtypeStruct
    key_sds = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(
        functools.partial(init_state, apply_fn, init_fn, opt, fed), key_sds)
    data_sds = {
        "x_train": sds((m, 64, s), jnp.int32),
        "y_train": sds((m, 64), jnp.int32),
        "x_ref": sds((m, r, s), jnp.int32),
        "y_ref": sds((m, r), jnp.int32),
    }

    def spec_like(sd):
        return NamedSharding(mesh, P("data", *([None] * (len(sd.shape) - 1))))

    state_shard = jax.tree.map(spec_like, state_sds)
    # scalars (rng, round) replicated
    state_shard = state_shard._replace(
        rng=NamedSharding(mesh, P()), round=NamedSharding(mesh, P()),
        commitments=NamedSharding(mesh, P("data")))
    data_shard = jax.tree.map(spec_like, data_sds)
    with mesh:
        lowered = jax.jit(segment_fn,
                          in_shardings=(state_shard, data_shard),
                          out_shardings=None).lower(state_sds, data_sds)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    print(json.dumps({
        "fed_round_clients": m,
        "client_arch": cfg.name,
        "ref_mode": ref_mode,
        "tiling": tiling,
        "reselect_every": reselect_every,
        "attack": attack,
        "mesh": "16x16",
        # analysis: host-ok — AOT cost_analysis dict, no device value
        "flops_per_device": float(cost.get("flops", 0)),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "ok": True}, indent=1))
    return compiled


def main(argv=None):
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist",
                    choices=["mnist", "aecg", "seeg"])
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dryrun", action="store_true",
                    help="lower a 256-client WPFed segment on the 16x16 mesh")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "kernel", "oracle", "ann"],
                    help="kernel-backed subsystem backend — drives both "
                         "selection AND exchange (DESIGN.md §4, §7); "
                         "'ann' switches SELECTION to the sub-quadratic "
                         "LSH-bucket candidate index (DESIGN.md §11) and "
                         "leaves exchange on auto")
    ap.add_argument("--ann-prefix-bits", type=int, default=-1,
                    help="ANN bucket prefix length (-1 = FedConfig "
                         "default; 0 = one-bucket exact fallback)")
    ap.add_argument("--ann-probes", type=int, default=-1,
                    help="ANN multi-probe bit flips — the recall knob "
                         "(-1 = FedConfig default)")
    ap.add_argument("--ref-mode", default="personal",
                    choices=["personal", "public"],
                    help="personal: each client's own reference set "
                         "(M*N forwards); public: one shared reference "
                         "set, exchange is a gather (DESIGN.md §7)")
    ap.add_argument("--tiling", default="auto",
                    choices=["auto", "oneshot", "tiled"],
                    help="kernel VMEM regime — drives both selection "
                         "AND exchange (DESIGN.md §10): oneshot holds "
                         "the full working set per program, tiled "
                         "streams VMEM-bounded tiles, auto picks from "
                         "the explicit VMEM estimate")
    ap.add_argument("--schedule", default="sync",
                    choices=["sync", "gossip"],
                    help="sync: re-select every round (the paper); "
                         "gossip: global re-selection every "
                         "--reselect-every rounds, cheap gossip epochs "
                         "in between (DESIGN.md §8)")
    ap.add_argument("--reselect-every", type=int, default=0,
                    help="gossip period G (0 = schedule default)")
    ap.add_argument("--attack", default="none",
                    choices=("none",) + THREATS,
                    help="in-graph threat model instrumenting the run "
                         "(core.adversary.resolve_threat, DESIGN.md §9)")
    ap.add_argument("--attack-frac", type=float, default=0.5,
                    help="fraction of clients that are attackers "
                         "(the tail of the client axis)")
    ap.add_argument("--attack-start", type=int, default=-1,
                    help="first attacked round (-1 = the threat's "
                         "registry default, e.g. poison's §4.8 warm-up)")
    ap.add_argument("--service", action="store_true",
                    help="run the continuous federation service "
                         "(repro.service, DESIGN.md §13) instead of a "
                         "fixed-round experiment")
    ap.add_argument("--periods", type=int, default=3,
                    help="[service] reselection periods to run")
    ap.add_argument("--churn", default="",
                    help="[service] churn events as "
                         "'period:kind:client,...' e.g. "
                         "'1:leave:4,2:join:5'")
    ap.add_argument("--gossip-counts", default="",
                    help="[service] per-client gossip budgets G_i as a "
                         "comma list (default: full period for all)")
    ap.add_argument("--staleness-lambda", type=float, default=0.5,
                    help="[service] Eq. 8 staleness discount "
                         "exp(-lambda * code_age)")
    ap.add_argument("--ckpt-dir", default="",
                    help="[service] checkpoint directory (durable "
                         "state + chain.json)")
    ap.add_argument("--keep-last-k", type=int, default=3,
                    help="[service] checkpoint retention")
    ap.add_argument("--resume", action="store_true",
                    help="[service] resume from the latest checkpoint "
                         "in --ckpt-dir")
    ap.add_argument("--faults", default="",
                    help="[service] deterministic fault-injection spec "
                         "'seed=7,drop=0.1,delay=0.1,corrupt=0.1,"
                         "straggle=0.2,publish_fail=0.3,crash=2,fork=1' "
                         "(core.faults.parse_fault_spec, DESIGN.md §15)")
    args = ap.parse_args(argv)
    if args.service:
        _, _, history = run_service_federation(
            args.dataset, periods=args.periods,
            reselect_every=args.reselect_every or 4,
            num_clients=args.clients, seed=args.seed, churn=args.churn,
            gossip_counts=args.gossip_counts,
            staleness_lambda=args.staleness_lambda,
            keep_last_k=args.keep_last_k,
            ckpt_dir=args.ckpt_dir or None, resume=args.resume,
            faults=args.faults)
        print(json.dumps(history[-3:], indent=1))
        return
    if args.dryrun:
        import os
        assert "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""), \
            "run with XLA_FLAGS=--xla_force_host_platform_device_count=512"
        sched = resolve_schedule(args.schedule, args.reselect_every)
        dryrun_fed_round(num_clients=args.clients or 256,
                         backend="kernel" if args.backend == "auto"
                         else args.backend,  # "ann" lowers the ann path
                         ref_mode=args.ref_mode, tiling=args.tiling,
                         reselect_every=sched.reselect_every,
                         attack=args.attack, attack_frac=args.attack_frac,
                         attack_start=args.attack_start)
        return
    _, history = run_federation(args.dataset, args.rounds,
                                num_clients=args.clients, seed=args.seed,
                                backend=args.backend,
                                ref_mode=args.ref_mode,
                                tiling=args.tiling,
                                schedule=args.schedule,
                                reselect_every=args.reselect_every,
                                attack=args.attack,
                                attack_frac=args.attack_frac,
                                attack_start=args.attack_start,
                                ann_prefix_bits=args.ann_prefix_bits,
                                ann_probes=args.ann_probes)
    print(json.dumps(history[-3:], indent=1))


if __name__ == "__main__":
    main()
