#!/usr/bin/env python3
"""Chip benchmark of the WPFed federation: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's "workloads": a configuration
(bench/configs/<config>.json with its plain reference <config>.py)
under a traffic mix (bench/workloads/<traffic>.json). The run

  1. keeps JAX's compile cache at .bench_cache/jax in the checkout;
  2. makes the cell's data and initial client states from --seed on
     the device (one jitted call each);
  3. drives the program's own period loop, `run_rounds` (WPFed
     program, `evaluate`, the chain publisher) or `run_service`, in
     ONE call: the first `warmup_periods` compile and are compared
     with the reference afterwards; the window opens at the next
     period boundary and closes at the first boundary --seconds later;
  4. with --trace 1 profiles a few periods after the warm-up instead,
     and reports the per-layer metrics (bench/metrics/<metric>.py);
  5. runs the plain reference over the warm-up's first periods and
     compares (bench/compare.py, limits in bench/limits/<cell>.json);
  6. prints each compared number beside its limit on stderr, then one
     JSON result line on stdout.

It exits non-zero and prints no result where JAX finds no TPU or
fewer chips than the cell asks for.

A configuration is two files, found by its name alone:

  <config>.json  "model": the client model block. Every key of it
                 reaches the program (`client_model_config`): those
                 the program's ClientModelConfig names as fields, and
                 all others in its `arch`, as sorted (key, value)
                 pairs. "input": "tokens" with "vocab": V makes the
                 traffic int32 ids in [0, V) of shape input_shape in
                 place of float rows (bench/traffic.py). "fed": the
                 FedConfig fields the configuration sets.
  <config>.py    the plain reference: `init(cfg, key)` one client's
                 trainable tree, the tree the program trains too;
                 `apply(p, x)` logits; `forward_flops(cfg)` one
                 example's forward FLOPs. Optionally
                 `init_shared(cfg, key)`: frozen weights that all
                 clients share, made once a run during set-up, given
                 to the program as data["shared"] and to the
                 reference as `apply(p, x, shared)`; and
                 `train_flops(cfg)`: one example's forward and
                 backward FLOPs of what is trained, which the work
                 count takes in place of 3 x forward_flops; and
                 `layer_work(cfg, wl)`: {name: (FLOPs, bytes)} of one
                 period of the configuration's own layers, which a
                 traced run hands its per-layer metrics as
                 ctx["layer_work"] ({} where the module has none).

A configuration cut from a published model lists each key it changed
in its BENCHMARK.json entry's "reduced" (names, never a width), and
its JSON then holds a "deployment" string: over how many chips each
layer is divided, and what this chip holds of it. A cell's "chips" is
1 or 4; at most half the cells, rounded down, and always one, may ask
for 4. A "cpu" block in the configuration's JSON (overrides of
"model", "fed" and "params") is the size the harness's own CPU tests
run it at (bench/tests/benchkit.py); no run of this command reads it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache", "jax")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import compare  # noqa: E402
import devtrace as tr  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

# A traced run profiles at least this many steady periods and seconds.
TRACE_PERIODS, TRACE_SECONDS = 3, 1.0

# folded into the seed's key for the shared weights; no other draw
# folds it in (the program and the reference fold in 1)
SHARED_FOLD = 0x53484152

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    pass


class StopWindow(Exception):
    """Raised from the period loop's `log` callback to end the run at
    a period boundary."""


# ------------------------------------------------------------------ files
def _json(path):
    with open(path) as f:
        return json.load(f)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfg = _json(os.path.join(root, conf["file"]))
    ref_path = os.path.splitext(os.path.join(root, conf["file"]))[0] + ".py"
    bench_dir = os.path.join(root, os.path.relpath(BENCH, ROOT))
    limits_path = os.path.join(bench_dir, "limits", name + ".json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name, "entry": entry, "cfg": cfg, "bench": bench_dir,
        "model": _module(ref_path, "model_" + re.sub(r"\W", "_",
                                                      conf["name"])),
        "wl": _json(os.path.join(bench_dir, "workloads",
                                 entry["traffic"] + ".json")),
        "limits": _json(limits_path) if os.path.exists(limits_path) else {},
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# ----------------------------------------------------------------- device
def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_cache() -> None:
    import jax
    os.makedirs(CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's trace and compile events while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


# ------------------------------------------------------------ the window
class Clock:
    """`log=` callback of the period loop: stamps each period's end,
    opens the window after `warmup` periods and ends the run with
    StopWindow at the first period end `seconds` later (or, when
    tracing, once TRACE_PERIODS periods and TRACE_SECONDS have been
    traced)."""

    def __init__(self, warmup, seconds, counter, trace_dir=None):
        self.warmup, self.seconds, self.counter = warmup, seconds, counter
        self.trace_dir = trace_dir
        self.stamps, self.lines, self.span = [], [], None

    def start(self):
        self.stamps = [time.perf_counter()]

    def window(self):
        return self.stamps[self.warmup:]

    def __call__(self, line):
        import jax
        now = time.perf_counter()
        self.stamps.append(now)
        self.lines.append(line)
        done = len(self.stamps) - 1
        if done == self.warmup:
            self.counter.armed = True
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self.span = jax.profiler.TraceAnnotation(tr.WINDOW)
                self.span.__enter__()
                self.stamps[-1] = time.perf_counter()
            return
        if done < self.warmup:
            return
        elapsed = now - self.stamps[self.warmup]
        if self.trace_dir:
            if (done - self.warmup >= TRACE_PERIODS
                    and elapsed >= TRACE_SECONDS):
                self.span.__exit__(None, None, None)
                self.counter.armed = False
                jax.profiler.stop_trace()
                raise StopWindow
        elif elapsed >= self.seconds:
            self.counter.armed = False
            raise StopWindow


class Capture:
    """Host copies of what the first `periods` periods produced, taken
    at each period's end, for the comparison with the reference."""

    def __init__(self, periods):
        self.periods = periods
        self.codes, self.rankings, self.m, self.params = [], [], None, None
        self.last = None
        self.seen = 0

    def __call__(self, fed_state):
        import jax
        import numpy as np
        self.last = fed_state
        if self.seen < self.periods:
            self.codes.append(np.asarray(fed_state.codes))
            self.rankings.append(np.asarray(fed_state.rankings))
            if self.seen == 0:
                self.m = [np.asarray(x) for x in
                          jax.tree.leaves(fed_state.opt_state["m"])]
            if self.seen == self.periods - 1:
                self.params = [np.asarray(x)
                               for x in jax.tree.leaves(fed_state.params)]
        self.seen += 1


def _logged(lines):
    """(acc, mean_loss) of each period's last round, from the loop's
    own log lines."""
    out = []
    for line in lines:
        acc = re.search(r"acc (\S+)", line)
        loss = re.search(r"mean_loss (\S+)", line)
        out.append((float(acc.group(1)), float(loss.group(1))))
    return out


# ----------------------------------------------------------- the program
def _frozen(value):
    """A JSON value as a hashable one: lists as tuples, objects as
    sorted (key, value) pairs."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, list):
        return tuple(_frozen(v) for v in value)
    return value


@functools.lru_cache(maxsize=None)
def _config_class():
    """The program's ClientModelConfig with an `arch` field, empty by
    default, that holds the model block's other keys: the program's
    own class once it has that field, until then a subclass that adds
    it and nothing else."""
    from repro.configs.paper_models import ClientModelConfig
    if "arch" in {f.name for f in dataclasses.fields(ClientModelConfig)}:
        return ClientModelConfig
    return dataclasses.make_dataclass(
        "ClientModelConfig",
        [("arch", tuple, dataclasses.field(default=()))],
        bases=(ClientModelConfig,), frozen=True)


def client_model_config(cfg):
    """The program's client model configuration from a configuration's
    `model` block: each key that names a field, the rest in `arch`."""
    cls = _config_class()
    fields = {f.name for f in dataclasses.fields(cls)} - {"name", "arch"}
    block = {k: _frozen(v) for k, v in cfg["model"].items()}
    return cls(name=cfg["name"],
               arch=tuple(sorted((k, v) for k, v in block.items()
                                 if k not in fields)),
               **{k: v for k, v in block.items() if k in fields})


def cell_data(cell, seed):
    """The cell's inputs from the seed: the traffic's stacked arrays
    and, where the configuration's module defines `init_shared`, under
    "shared" the frozen weights all clients share, made once on the
    device in one jitted call."""
    import jax
    data = traffic.generate(cell["cfg"], cell["wl"], seed)
    init_shared = getattr(cell["model"], "init_shared", None)
    if init_shared is not None:
        key = jax.random.fold_in(traffic.seed_key(seed), SHARED_FOLD)
        data["shared"] = jax.jit(
            functools.partial(init_shared, cell["cfg"]))(key)
    return data


def program_parts(cell, seed):
    """The system under test, configured from the cell's files, with
    its data and initial state made from the seed."""
    import jax
    from repro.configs.paper_models import FedConfig, recommended_dedupe
    from repro.core import init_state
    from repro.models import apply_client_model
    from repro.optim import adam

    cfg, wl = cell["cfg"], cell["wl"]
    apply_fn = functools.partial(apply_client_model,
                                 client_model_config(cfg))
    init_fn = functools.partial(cell["model"].init, cfg)
    m, ref_mode = wl["clients"], wl["ref_mode"]
    fed = FedConfig(num_clients=m, ref_mode=ref_mode,
                    dedupe_rankings=recommended_dedupe(ref_mode),
                    **cfg["fed"])
    opt = adam(fed.lr)
    data = cell_data(cell, seed)
    state = jax.jit(functools.partial(init_state, apply_fn, init_fn, opt,
                                      fed))(traffic.seed_key(seed))
    jax.block_until_ready((data, state))
    return apply_fn, fed, opt, data, state


def drive_rounds(cell, apply_fn, fed, opt, data, held, clock, capture):
    """`run_rounds` wired as `run_federation` wires it. The initial
    state comes in a list the driver empties, so that only the period
    loop holds it."""
    import jax
    from repro.core import evaluate, resolve_schedule, run_rounds
    from repro.core import wpfed_program
    from repro.core.chain import Blockchain
    from repro.launch.fed import chain_publisher

    wl = cell["wl"]
    sched = resolve_schedule(wl["schedule"], wl["reselect_every"])
    chain = Blockchain()
    publish = chain_publisher(chain, fed.num_clients)

    def on_reselect(r0, st):
        with jax.profiler.TraceAnnotation("bench.publish"):
            publish(r0, st)
        capture(st)

    clock.start()
    try:
        run_rounds(wpfed_program(apply_fn, opt, fed), held.pop(), data,
                   rounds=sched.reselect_every * 10 ** 7, schedule=sched,
                   eval_fn=lambda st, d: {"acc": evaluate(
                       apply_fn, st, d)["mean_acc"]},
                   on_reselect=on_reselect, log=clock)
    except StopWindow:
        pass
    return {"chain": chain}


def drive_service(cell, apply_fn, fed, opt, data, held, clock, capture,
                  ckpt_dir):
    """`run_service` wired as `run_service_federation` wires it, with a
    transport that notes each period's state as it collects it."""
    import jax
    import jax.numpy as jnp
    from repro.core import evaluate
    from repro.core.chain import Blockchain
    from repro.service import ServiceConfig, init_service_state, run_service
    from repro.service.transport import BulletinTransport

    wl = cell["wl"]
    svc = ServiceConfig(reselect_every=wl["reselect_every"],
                        staleness_lambda=wl["staleness_lambda"],
                        checkpoint_every=wl["checkpoint_every"],
                        keep_last_k=wl["keep_last_k"])
    seen = {}

    class NotingTransport(BulletinTransport):
        def collect(self, period, announcing, st):
            with jax.profiler.TraceAnnotation("bench.collect"):
                out = super().collect(period, announcing, st)
            if (period + 1) % svc.checkpoint_every == 0:
                seen["state"], seen["period"] = st, period
            capture(st.fed)
            return out

        def publish(self, *a, **k):
            with jax.profiler.TraceAnnotation("bench.publish"):
                return super().publish(*a, **k)

        def fetch(self, *a, **k):
            with jax.profiler.TraceAnnotation("bench.fetch"):
                return super().fetch(*a, **k)

    transport = NotingTransport(Blockchain())
    clock.start()
    try:
        run_service(apply_fn, opt, fed, svc,
                    init_service_state(held.pop(), svc), data,
                    periods=10 ** 7, ckpt_dir=ckpt_dir,
                    eval_fn=lambda st, d: {"acc": evaluate(
                        apply_fn, st.fed, d,
                        honest_mask=st.active.astype(jnp.float32)
                    )["mean_acc"]},
                    log=clock, transport=transport)
    except StopWindow:
        pass
    return {"chain": transport.chain, "service_state": seen.get("state"),
            "period": seen.get("period")}


def ledger_fault(chain, fed_state) -> float:
    """0 when the chain verifies and its last block holds the state's
    codes and rankings, else 1."""
    import numpy as np
    if not chain.verify_chain():
        return 1.0
    payload = chain.blocks[-1].payload
    codes = np.asarray(fed_state.codes).astype("<u4")
    rankings = np.asarray(fed_state.rankings)
    for i in range(codes.shape[0]):
        ann = payload["announcements"].get(str(i), {})
        if ann.get("lsh") != codes[i].tobytes().hex():
            return 1.0
        if payload["reveals"].get(str(i)) != [int(x) for x in rankings[i]]:
            return 1.0
    return 0.0


def ckpt_fault(ckpt_dir, svc_state, period) -> float:
    """0 when the newest checkpoint is of `period`, the last period due
    one, and restores bitwise to the state it was written from, else 1."""
    import jax
    import numpy as np
    from repro.checkpoint import store
    if period is None or store.latest_step(ckpt_dir) != period:
        return 1.0
    got = jax.tree.leaves(store.restore(ckpt_dir, period, svc_state))
    want = jax.tree.leaves(svc_state)
    same = len(got) == len(want) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(got, want))
    return 0.0 if same else 1.0


# ------------------------------------------------------------- reference
def reference_readings(cell, seed, data, periods, dtype=None, fault=None):
    """The plain reference over the compared periods, as the numbers
    `compare.numbers` reads."""
    import jax
    import jax.numpy as jnp
    import reference

    wl = cell["wl"]
    fed_ref = reference.Federation(
        cell["model"], cell["cfg"], wl["clients"],
        wl["ref_mode"] == "public", dtype=dtype or jnp.float32,
        fault=fault)
    states, rounds = fed_ref.run(traffic.seed_key(seed), data, periods,
                                 wl["reselect_every"])
    g = wl["reselect_every"]
    last = [rounds[(k + 1) * g - 1] for k in range(periods)]
    leaves = lambda t: [jax.device_get(x) for x in jax.tree.leaves(t)]
    return {
        "loss": [r["loss"] for r in last], "acc": [r["acc"] for r in last],
        "codes": [jax.device_get(s.codes) for s in states[1:]],
        "rankings": [jax.device_get(s.rankings) for s in states[1:]],
        "m": leaves(states[1].opt["m"]),
        "delta": [a.astype("f8") - b.astype("f8") for a, b in
                  zip(leaves(states[-1].params), leaves(states[0].params))],
    }


# --------------------------------------------------------------- metrics
def period_stats(clock, rounds_per_period, clients):
    import numpy as np
    w = clock.window()
    periods = np.diff(w)
    return {
        "client_rounds_per_s": clients * rounds_per_period * len(periods)
        / (w[-1] - w[0]),
        "period_p90_ms": float(np.percentile(periods, 90)) * 1e3,
        "periods": int(len(periods)),
    }


def per_layer(cell, ctx):
    out = {}
    for metric in cell["per_layer"]:
        path = os.path.join(cell.get("bench", BENCH), "metrics",
                            metric["name"] + ".py")
        value = _module(path, "metric_" + re.sub(r"\W", "_",
                                                  metric["name"])).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def period_flops(cell, n):
    """Client-model FLOPs of one period with `n` neighbors: the
    configuration's counts (`forward_flops`, and `train_flops` where
    its module defines it) through `work.round_flops`."""
    wl, cfg, model = cell["wl"], cell["cfg"], cell["model"]
    train = getattr(model, "train_flops", None)
    return wl["reselect_every"] * work.round_flops(
        model.forward_flops(cfg), wl["clients"], n, cfg["fed"], wl,
        wl["ref_mode"] == "public",
        train_flops=None if train is None else train(cfg))


def trace_context(cell, events, clock, device, pk, fed):
    """What the per-layer readers see of a traced run."""
    import numpy as np
    wl, cfg = cell["wl"], cell["cfg"]
    layer_work = getattr(cell["model"], "layer_work", None)
    m = wl["clients"]
    n = min(fed.num_neighbors, m - 1)
    c = cfg["model"]["num_classes"]
    return {
        "events": events, "window": tr.window(events),
        "periods_s": list(np.diff(clock.window())),
        "period_flops": period_flops(cell, n),
        "lsh": work.lsh_work(m, cfg["params"], cfg["fed"]["lsh_bits"]),
        "exchange": work.exchange_work(m, n, wl["ref_rows"], c),
        "layer_work": {} if layer_work is None else layer_work(cfg, wl),
        "peaks": pk, "memory_peak_bytes": device["memory_peak_bytes"],
    }


# ------------------------------------------------------------------ main
def run(cell, seed, seconds, trace, check_out=sys.stderr):
    """One run of a cell; returns the result line's object."""
    import jax
    import numpy as np

    device = device_info(cell["entry"]["chips"])
    pk = work.peaks(device["kind"])
    use_cache()
    counter = CompileCounter()
    wl = cell["wl"]
    periods_cmp = wl["check_periods"]
    apply_fn, fed, opt, data, state = program_parts(cell, seed)
    p0 = [np.asarray(x) for x in jax.tree.leaves(state.params)]
    capture = Capture(periods_cmp)
    with tempfile.TemporaryDirectory() as tdir:
        clock = Clock(wl["warmup_periods"], seconds, counter,
                      trace_dir=tdir if trace else None)
        os.makedirs(os.path.join(ROOT, "results"), exist_ok=True)
        ckpt_dir = tempfile.mkdtemp(dir=os.path.join(ROOT, "results"),
                                    prefix="bench-ckpt-")
        try:
            args = (cell, apply_fn, fed, opt, data, [state], clock, capture)
            del state
            if wl["driver"] == "service":
                ran = drive_service(*args, ckpt_dir)
            else:
                ran = drive_rounds(*args)
            setup_s = clock.stamps[wl["warmup_periods"]] - T_START
            stats = jax.devices()[0].memory_stats() or {}
            device["memory_peak_bytes"] = int(stats.get(
                "peak_bytes_in_use", 0))
            found = {"ledger": ledger_fault(ran["chain"], capture.last)}
            if "service_state" in ran:
                found["ckpt"] = ckpt_fault(ckpt_dir, ran["service_state"],
                                           ran["period"])
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        events = tr.load(tdir) if trace else None
    ran = capture.last = None
    logged = _logged(clock.lines[:periods_cmp])
    prog = {"acc": [a for a, _ in logged], "loss": [l for _, l in logged],
            "codes": capture.codes, "rankings": capture.rankings,
            "m": capture.m,
            "delta": [a.astype("f8") - b.astype("f8")
                      for a, b in zip(capture.params, p0)]}
    ref = reference_readings(cell, seed, data, periods_cmp)
    found.update(compare.numbers(prog, ref))
    checks = compare.judge(found, cell["limits"])
    correct = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    for name in sorted(found):
        lim = cell["limits"].get(name)
        print(f"{name} {found[name]!r} limit "
              f"{'not compared' if lim is None else repr(lim)}",
              file=check_out)
    print(f"compile events in window: {counter.count}", file=check_out)
    result = {"correct": correct}
    if trace:
        lo, hi = tr.window(events) or (0, 0)
        device["busy_s"] = tr.busy_ns(events, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx = trace_context(cell, events, clock, device, pk, fed)
        metrics = per_layer(cell, ctx)
        result["breakdown"] = tr.breakdown(events, lo, hi)
        n_periods = len(clock.window()) - 1
    else:
        st = period_stats(clock, wl["reselect_every"], wl["clients"])
        n_periods = st["periods"]
        print(f"window periods: {n_periods}", file=check_out)
        metrics = {"client_rounds_per_s": st["client_rounds_per_s"],
                   "period_p90_ms": st["period_p90_ms"],
                   "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in metrics.items() if k in units}
    result.update({
        "attempted": n_periods,
        "failed": sum(c["value"] > c["limit"] for c in checks.values()),
        "metrics": metrics, "device": device, "checks": checks})
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=check_out)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
