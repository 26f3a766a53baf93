#!/usr/bin/env python3
"""Chip smoke run: the federation's main path, once, on one TPU chip.

    python chip_smoke.py

Runs in ONE process (JAX is imported once; nothing here starts a child
that needs the chip) through the entry points a user calls:

  A. Paper federation: mnist-cnn clients at the paper's setting (M=10,
     N=12 from PAPER_FED_OPTIMA, clamped to M-1; personal reference),
     gossip schedule G=2 for 4 rounds through `run_federation` with the
     backends left on "auto", which must resolve to the compiled Pallas
     kernels. The same federation then runs on the jnp oracles, and the
     two are compared: per-round accuracy and loss, plus one LSH +
     selection + exchange step of each on identical inputs (code bits,
     selection ids, l_ij, the §3.5 mask, the distillation target).
  B. Larger federation: aecg-tcn at M=1024, public reference, 2 rounds
     (many selection row blocks, the M-forward exchange), with the same
     identical-input comparison.
  C. Service: `run_service_federation` on mnist M=10 for 2 periods with
     a checkpoint; a second service is killed after period 1, resumed
     from disk and must end bitwise equal to the uninterrupted one.
     A `PersonalizedServer` built from the final models then answers 64
     requests across all clients, each checked against `apply_fn` on
     that client's parameters.

Each phase prints its compile time (trace + lower + backend compile, as
JAX reports them), its wall times, the resolved backends and tilings,
and the device's peak bytes in use. Any failed check raises, so the run
exits non-zero. The last line of stdout, printed only when every phase
passed, is {"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}. Where JAX finds no TPU, the run stops before any phase.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances on the chip, set from the first v5e runs (PERF.md §6):
# kernel and oracle then agreed bitwise on codes, ids, the §3.5 mask
# and the 4-round trajectory, with l_ij within 1.8e-7 and the target
# within 3.2e-6 relative. Each bound leaves room for last-ulp exp/log
# and MXU-pass differences (bit-exactness is pinned only in the CPU
# interpreter, by the tests) without admitting a wrong kernel.
TOL = {
    "code_bits_differ_frac": 1e-3,   # identical params -> LSH sign bits
    "ids_differ_frac": 1e-3,         # identical codes/scores -> top-N ids
    "valid_differ_frac": 1e-3,       # §3.5 mask (flips only on near-ties)
    "l_ij_rel": 1e-5,                # Eq. 3 losses on identical logits
    "target_rel": 1e-4,              # distillation target (a 0/1 mean)
    "acc_abs": 0.01,                 # per-round mean accuracy, 4 rounds
    "loss_rel": 1e-3,                # per-round mean loss, 4 rounds
    "eval_acc_abs": 0.01,            # round's reported acc vs reference
    "serve_abs": 1e-4,               # served logits vs apply_fn
}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Sums JAX's compile-time events (trace, lower, backend compile)
    per phase; `phase` names the phase the events are charged to."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.phase = "set-up"
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds[self.phase] += duration


class PeriodLog:
    """`log=` callback for the federation drivers: prints each period's
    line and stamps the wall clock, so period i's wall time is the gap
    between stamps i-1 and i (stamp -1 is the call's start)."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self.stamps = [time.perf_counter()]

    def __call__(self, line: str) -> None:
        self.stamps.append(time.perf_counter())
        print(f"{self.prefix} {line}", flush=True)

    def periods(self):
        return [b - a for a, b in zip(self.stamps, self.stamps[1:])]


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = np.maximum(np.abs(b), 1e-30)
    return float(np.max(np.abs(a - b) / scale)) if a.size else 0.0


def resolved(fed, m: int, r: int, c: int) -> dict:
    """The backends and tilings the round resolves for this config, by
    the same calls `select_partners` and `all_in_one_exchange` make."""
    from repro.core import ann, backends
    n = min(fed.num_neighbors, m - 1)
    bits_tot = fed.lsh_bits
    k = ann.candidate_count(m, fed.ann_prefix_bits, fed.ann_probes, n,
                            bits_tot)
    sel = backends.resolve_selection(
        fed.selection_backend, m,
        exact_flops=backends.selection_flops(m, bits_tot),
        ann_flops=backends.ann_selection_flops(m, bits_tot, k))
    return {
        "selection": sel,
        "selection_tiling": backends.resolve_tiling(
            fed.selection_tiling, backends.selection_vmem_bytes(m, bits_tot)),
        "exchange": backends.resolve(fed.exchange_backend),
        "exchange_tiling": backends.resolve_tiling(
            fed.exchange_tiling, backends.exchange_vmem_bytes(n, r, c)),
        "interpret": backends.interpret(),
    }


def paper_fed(dataset: str, num_clients: int, ref_mode: str, rounds: int):
    """The FedConfig `run_federation` builds for a dataset (paper Table 1
    optima), made explicit so both backends and the identical-input
    comparison share it."""
    from repro.configs.paper_models import (FedConfig, PAPER_FED_OPTIMA,
                                            recommended_dedupe)
    n_opt, alpha, gamma = PAPER_FED_OPTIMA[dataset]
    return FedConfig(num_clients=num_clients, num_neighbors=n_opt,
                     alpha=alpha, gamma=gamma, rounds=rounds,
                     ref_mode=ref_mode,
                     dedupe_rankings=recommended_dedupe(ref_mode))


def oracle_of(fed):
    return dataclasses.replace(fed, selection_backend="oracle",
                               exchange_backend="oracle")


def dataset_arrays(dataset: str, num_clients: int, seed: int = 0):
    from repro.data import DATASETS
    ds = DATASETS[dataset](num_clients=num_clients, seed=seed)
    return {k: jnp.asarray(v) for k, v in ds.stacked().items()}


def client_apply(dataset: str):
    from repro.launch.fed import MODEL_FOR
    from repro.models import apply_client_model
    return functools.partial(apply_client_model, MODEL_FOR[dataset]())


def one_step_agreement(apply_fn, fed, state, data) -> dict:
    """Kernel vs oracle on identical inputs: the LSH codes of `state`'s
    params, then select_phase and exchange_phase on `state`. Checks
    that the kernel program really holds Mosaic kernels."""
    from repro.core import exchange_phase, lsh, select_phase

    def step(f):
        def run(state, data):
            codes = lsh.stacked_lsh_codes(state.params, seed=state.round,
                                          bits=f.lsh_bits,
                                          backend=f.selection_backend)
            sel = select_phase(state, f)
            exch = exchange_phase(apply_fn, f, state.params, data, sel)
            return codes, sel.ids, exch
        return jax.jit(run)

    kernel_step, oracle_step = step(fed), step(oracle_of(fed))
    lowered = kernel_step.lower(state, data).as_text()
    check(lowered.count("tpu_custom_call") >= 3,
          "kernel step lowers to LSH, selection and exchange kernels")
    ck, ik, ek = jax.block_until_ready(kernel_step(state, data))
    co, io, eo = jax.block_until_ready(oracle_step(state, data))
    xor = np.ascontiguousarray(
        np.bitwise_xor(np.asarray(ck), np.asarray(co)))
    bits_differ = int(np.unpackbits(xor.view(np.uint8)).sum())
    return {
        "code_bits_differ": bits_differ,
        "code_bits_differ_frac": bits_differ / (xor.size * 32),
        "ids_differ_frac": float(np.mean(np.asarray(ik) != np.asarray(io))),
        "l_ij_rel": rel_err(ek.l_ij, eo.l_ij),
        "valid_differ_frac": float(np.mean(
            np.asarray(ek.valid_mask) != np.asarray(eo.valid_mask))),
        "target_rel": rel_err(ek.target_ref, eo.target_ref),
    }


def reference_accuracy(apply_fn, params, data) -> float:
    """Mean test accuracy from one un-batched forward per client: the
    reference for `evaluate`'s vmapped forward."""
    fwd = jax.jit(apply_fn)
    accs = []
    for i in range(data["x_test"].shape[0]):
        row = jax.tree.map(lambda p: p[i], params)
        logits = fwd(row, data["x_test"][i])
        accs.append(float(jnp.mean(jnp.argmax(logits, -1)
                                   == data["y_test"][i])))
    return float(np.mean(accs))


def check_agreement(agree: dict, what: str) -> None:
    for key in ("code_bits_differ_frac", "ids_differ_frac", "l_ij_rel",
                "valid_differ_frac", "target_rel"):
        check(agree[key] <= TOL[key],
              f"{what}: {key} {agree[key]!r} > {TOL[key]!r}")


def report(phase: str, clock: CompileClock, **fields) -> None:
    fields = {"phase": phase,
              "compile_s": round(clock.seconds[phase], 3),
              "peak_bytes_in_use": peak_bytes(), **fields}
    print(json.dumps(fields, default=str), flush=True)


def phase_a(clock: CompileClock) -> None:
    from repro.launch.fed import run_federation
    clock.phase = "A"
    fed = paper_fed("mnist", 10, "personal", rounds=4)
    res = resolved(fed, m=10, r=64, c=10)
    print(f"A: resolved {res}", flush=True)
    check(res["selection"] == "kernel" and res["exchange"] == "kernel",
          "auto resolves selection and exchange to the kernels on TPU")
    check(res["interpret"] is False, "kernels compile (no interpreter)")

    runs = {}
    for name, f in (("kernel", fed), ("oracle", oracle_of(fed))):
        log = PeriodLog(f"A[{name}]")
        state, history = run_federation(
            "mnist", rounds=4, fed=f, schedule="gossip", reselect_every=2,
            log=log)
        runs[name] = (state, history, log.periods())
    (state, hist_k, periods_k), (_, hist_o, periods_o) = \
        runs["kernel"], runs["oracle"]
    check(len(hist_k) == 4 and len(hist_o) == 4, "4 rounds each")
    for h in hist_k:
        check(np.isfinite(h["mean_loss"]) and 0.0 <= h["acc"] <= 1.0,
              f"round {h['round']} metrics finite: {h}")
    acc_abs = max(abs(a["acc"] - b["acc"]) for a, b in zip(hist_k, hist_o))
    loss_rel = max(abs(a["mean_loss"] - b["mean_loss"])
                   / abs(b["mean_loss"]) for a, b in zip(hist_k, hist_o))
    data = dataset_arrays("mnist", 10)
    agree = one_step_agreement(client_apply("mnist"), fed, state, data)
    acc_ref = reference_accuracy(client_apply("mnist"), state.params, data)
    report("A", clock, resolved=res, acc_reference_last=acc_ref,
           first_period_s_kernel=periods_k[0],
           steady_period_s_kernel=periods_k[1:],
           first_period_s_oracle=periods_o[0],
           steady_period_s_oracle=periods_o[1:],
           acc_kernel=[h["acc"] for h in hist_k],
           acc_oracle=[h["acc"] for h in hist_o],
           loss_kernel=[h["mean_loss"] for h in hist_k],
           loss_oracle=[h["mean_loss"] for h in hist_o],
           acc_abs_max=acc_abs, loss_rel_max=loss_rel,
           one_step_agreement=agree, tolerances=TOL)
    check(acc_abs <= TOL["acc_abs"], f"A: per-round acc differs {acc_abs}")
    check(loss_rel <= TOL["loss_rel"],
          f"A: per-round loss differs {loss_rel}")
    check_agreement(agree, "A")
    check(abs(hist_k[-1]["acc"] - acc_ref) <= TOL["eval_acc_abs"],
          f"A: reported acc {hist_k[-1]['acc']} vs per-client reference "
          f"{acc_ref}")


def phase_b(clock: CompileClock, num_clients: int = 1024) -> None:
    from repro.launch.fed import run_federation
    clock.phase = "B"
    fed = paper_fed("aecg", num_clients, "public", rounds=2)
    data = dataset_arrays("aecg", num_clients)
    r, c = data["x_ref"].shape[1], 2
    res = resolved(fed, m=num_clients, r=r, c=c)
    print(f"B: resolved {res}", flush=True)
    check(res["selection"] == "kernel" and res["exchange"] == "kernel",
          "B: selection and exchange on the kernels")
    log = PeriodLog("B")
    state, history = run_federation("aecg", rounds=2, fed=fed,
                                    num_clients=num_clients, log=log)
    periods = log.periods()
    for h in history:
        check(np.isfinite(h["mean_loss"]) and 0.0 <= h["acc"] <= 1.0,
              f"B: round {h['round']} metrics finite: {h}")
    check(state.codes.shape == (num_clients, fed.lsh_bits // 32),
          "B: one code per client")
    agree = one_step_agreement(client_apply("aecg"), fed, state, data)
    acc_ref = reference_accuracy(client_apply("aecg"), state.params, data)
    report("B", clock, resolved=res, num_clients=num_clients,
           acc_reference_last=acc_ref,
           ref_rows=r, first_period_s=periods[0],
           steady_period_s=periods[1:],
           acc=[h["acc"] for h in history],
           loss=[h["mean_loss"] for h in history],
           one_step_agreement=agree)
    check_agreement(agree, "B")
    check(abs(history[-1]["acc"] - acc_ref) <= TOL["eval_acc_abs"],
          f"B: reported acc {history[-1]['acc']} vs per-client reference "
          f"{acc_ref}")


def phase_c(clock: CompileClock) -> None:
    from repro.launch.fed import run_service_federation
    from repro.service import PersonalizedServer
    clock.phase = "C"
    kw = dict(dataset="mnist", reselect_every=2, num_clients=10)
    scratch = os.path.join(ROOT, "results")
    os.makedirs(scratch, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=scratch) as tmp:
        log = PeriodLog("C[straight]")
        s_a, chain_a, hist_a = run_service_federation(
            periods=2, ckpt_dir=os.path.join(tmp, "a"), log=log, **kw)
        periods = log.periods()
        ckpt_b = os.path.join(tmp, "b")
        run_service_federation(periods=1, ckpt_dir=ckpt_b,
                               log=PeriodLog("C[killed]"), **kw)
        s_b, chain_b, hist_b = run_service_federation(
            periods=2, ckpt_dir=ckpt_b, resume=True,
            log=PeriodLog("C[resumed]"), **kw)
    check(hist_b == hist_a[-len(hist_b):],
          "C: resumed period's metrics equal the uninterrupted run's")
    leaves_a, leaves_b = jax.tree.leaves(s_a), jax.tree.leaves(s_b)
    check(len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves_a, leaves_b)),
        "C: resumed final state bitwise equal to uninterrupted")
    check(chain_b.verify_chain(), "C: ledger verifies across the restart")

    apply_fn = client_apply("mnist")
    data = dataset_arrays("mnist", 10)
    x_test = data["x_test"]
    params = s_a.fed.params
    acc_ref = reference_accuracy(apply_fn, params, data)
    server = PersonalizedServer(apply_fn, params)
    requests = [(i % 10, x_test[i % 10, i // 10]) for i in range(64)]
    serve_s = []
    for _ in range(2):                 # the first flush compiles
        for cid, x in requests:
            server.submit(cid, x)
        t0 = time.perf_counter()
        got = server.flush()
        serve_s.append(time.perf_counter() - t0)
    worst = 0.0
    for (cid, x), out in zip(requests, got):
        row = jax.tree.map(lambda p, i=cid: p[i], params)
        want = np.asarray(apply_fn(row, x[None])[0])
        check(out.shape == want.shape and np.all(np.isfinite(out)),
              f"C: served logits for client {cid} finite")
        worst = max(worst, float(np.max(np.abs(out - want))))
    report("C", clock, acc_reference_last=acc_ref,
           periods_s_straight=periods,
           bitwise_resume=True, served=len(got),
           serve_flush_s=serve_s, serve_max_abs_err=worst)
    check(abs(hist_a[-1]["acc"] - acc_ref) <= TOL["eval_acc_abs"],
          f"C: reported acc {hist_a[-1]['acc']} vs per-client reference "
          f"{acc_ref}")
    check(len(got) == 64, "C: 64 requests answered")
    check(worst <= TOL["serve_abs"],
          f"C: served logits differ from apply_fn by {worst}")


def main() -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device.platform!r} ({device.device_kind})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    for run in (phase_a, phase_b, phase_c):
        t = time.perf_counter()
        run(clock)
        print(f"phase {run.__name__[-1].upper()} passed in "
              f"{time.perf_counter() - t:.1f}s", flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
