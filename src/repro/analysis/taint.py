"""Layer 3: jaxpr-level privacy-taint dataflow analysis (DESIGN.md §14).

Proves the paper's trust-free disclosure boundary as a machine-checked
property of the actual computation graphs: private sources (the local
parameter pytree, local data batches, optimizer state) are tainted at
the avals of each analysis target's signature, taint propagates
structurally through every eqn of `jax.make_jaxpr`'s output —
including `scan` / `while` / `cond` / `pjit` sub-jaxprs (carry
fixpoints, branch unions, predicate implicit flows), `pallas_call`
(conservatively: all inputs flow to all outputs), and `io_callback`
operands — and only the registered `@declassifier` functions
(`repro.analysis.privacy`) clear it. A tainted value reaching a
declared `sink(...)` is a `taint-sink` finding; a tainted `io_callback`
operand is a `taint-callback` finding; a target that fails to trace is
a `taint-trace-error`.

The lattice is the powerset of source labels ({client-params,
opt-state, client-data}) ordered by inclusion; every transfer function
below is a monotone union, so the scan/while carry fixpoints converge
in at most |labels| passes. Fixpoint iterations run with finding
emission off and are followed by one final emitting pass, so each
violation is reported exactly once.

Analysis targets are jaxprs of the real protocol entry points:
`head_targets()` covers every WPFed phase, the wpfed/baseline round
programs, a metrics-tapped compiled segment (scan + ordered
io_callback), the adversary-instrumented round, the continuous-service
round/segment (ledger publish path), and the PersonalizedServer
forward — traced over a tiny 4-client federation on the oracle
backends (the taint semantics are backend-invariant; oracle jaxprs are
small and pallas-free). Fixtures register their own targets via
`taint_target(...)`, captured in isolation by `capture_targets`.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, List, Sequence, Tuple

from repro.analysis import privacy
from repro.analysis.report import Finding

# canonical private-source labels (DESIGN.md §14 table)
SRC_PARAMS = "client-params"
SRC_OPT = "opt-state"
SRC_DATA = "client-data"
SOURCES = (SRC_PARAMS, SRC_OPT, SRC_DATA)

EMPTY: frozenset = frozenset()

# callback primitives whose operands cross to the host
_CALLBACK_PRIMS = ("io_callback", "pure_callback", "debug_callback")


# ---------------------------------------------------------------------------
# marker primitives (bound by repro.analysis.privacy while tracing)
# ---------------------------------------------------------------------------
def _make_marker(prim_name: str):
    from jax.extend.core import Primitive
    from jax.interpreters import ad, batching

    prim = Primitive(prim_name)
    prim.def_impl(lambda x, **_: x)
    prim.def_abstract_eval(lambda x, **_: x)
    # identity rules so markers survive vmap (declassifiers run under
    # jax.vmap — make_ranking) and autodiff without special-casing
    batching.primitive_batchers[prim] = \
        lambda args, dims, **params: (prim.bind(args[0], **params),
                                      dims[0])
    ad.defjvp(prim, lambda g, x, **params: g)
    ad.primitive_transposes[prim] = lambda ct, x, **params: [ct]
    return prim


taint_declassify_p = _make_marker("taint_declassify")
taint_sink_p = _make_marker("taint_sink")


def declassify_value(value, name: str):
    """Bind the declassify marker on every array leaf of `value`."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda leaf: taint_declassify_p.bind(jnp.asarray(leaf),
                                             name=name), value)


def sink_value(value, name: str):
    """Bind the sink marker on every array leaf of `value`."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda leaf: taint_sink_p.bind(jnp.asarray(leaf), name=name),
        value)


# ---------------------------------------------------------------------------
# the dataflow engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Ctx:
    """Per-analysis context threaded through sub-jaxpr recursion."""
    target: str
    findings: List[Finding]
    emit: bool = True

    def quiet(self) -> "_Ctx":
        return dataclasses.replace(self, emit=False)


def _fmt(taint: frozenset) -> str:
    return "{" + ", ".join(sorted(taint)) + "}"


def _rel(path: str) -> str:
    try:
        rel = os.path.relpath(path)
    except ValueError:
        return path
    return path if rel.startswith("..") else rel


_ANALYSIS_FILES = (os.path.join("analysis", "taint.py"),
                   os.path.join("analysis", "privacy.py"))


def _eqn_loc(eqn) -> Tuple[str, int]:
    """Source location of an eqn, best-effort (file, line). Marker
    primitives bind inside this module's tree.map, so frames from the
    analysis layer itself are skipped — the finding points at the
    protocol code that reached the sink. An eqn without a traceback
    has no frames and falls back to "<jaxpr>"; a change of JAX's
    source-info API raises here rather than blanking every location."""
    from jax._src import source_info_util
    fallback = None
    for frame in source_info_util.user_frames(eqn.source_info.traceback):
        loc = _rel(frame.file_name), int(frame.start_line)
        if fallback is None:
            fallback = loc
        if not frame.file_name.endswith(_ANALYSIS_FILES):
            return loc
    if fallback is not None:
        return fallback
    return "<jaxpr>", 0


def _is_literal(atom) -> bool:
    from jax.extend.core import Literal
    return isinstance(atom, Literal)


def _union(taints: Sequence[frozenset]) -> frozenset:
    return frozenset().union(*taints) if taints else EMPTY


def _call_jaxpr(params: dict):
    """The single sub-jaxpr of a call-like eqn (pjit, closed_call,
    custom_jvp/vjp, remat), as a ClosedJaxpr, or None."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if isinstance(sub, ClosedJaxpr):
            return sub
        if isinstance(sub, Jaxpr):
            return ClosedJaxpr(sub, ())
    return None


def _eval_jaxpr(jaxpr, in_taints: List[frozenset], ctx: _Ctx,
                const_taints=None) -> List[frozenset]:
    """Propagate taint through one (open) jaxpr; returns outvar taints."""
    env: Dict = {}
    consts = list(const_taints) if const_taints is not None \
        else [EMPTY] * len(jaxpr.constvars)
    for v, t in zip(jaxpr.constvars, consts):
        env[v] = t
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t

    def read(atom) -> frozenset:
        return EMPTY if _is_literal(atom) else env.get(atom, EMPTY)

    for eqn in jaxpr.eqns:
        outs = _eval_eqn(eqn, [read(a) for a in eqn.invars], ctx)
        for v, t in zip(eqn.outvars, outs):
            env[v] = t
    return [read(v) for v in jaxpr.outvars]


def _eval_eqn(eqn, ins: List[frozenset], ctx: _Ctx) -> List[frozenset]:
    name = eqn.primitive.name
    union = _union(ins)

    if name == "taint_declassify":
        # a registered declassifier's output: taint cleared by decree,
        # with the justification recorded in privacy.DECLASSIFIERS
        return [EMPTY for _ in eqn.outvars]

    if name == "taint_sink":
        snk = eqn.params.get("name", "?")
        if ins and ins[0] and ctx.emit:
            path, line = _eqn_loc(eqn)
            ctx.findings.append(Finding(
                "taint-sink", path, line,
                f"{ctx.target}: sink {snk!r} receives a value tainted "
                f"by {_fmt(ins[0])} with no declassifier on the path"))
        return list(ins)

    if name in _CALLBACK_PRIMS:
        if union and ctx.emit:
            path, line = _eqn_loc(eqn)
            ctx.findings.append(Finding(
                "taint-callback", path, line,
                f"{ctx.target}: {name} operand tainted by "
                f"{_fmt(union)} crosses to the host undeclassified"))
        return [union for _ in eqn.outvars]

    if name == "scan":
        return _eval_scan(eqn, ins, ctx)
    if name == "while":
        return _eval_while(eqn, ins, ctx)
    if name == "cond":
        return _eval_cond(eqn, ins, ctx)
    if name == "pallas_call":
        # conservative: every output may depend on every input (the
        # kernel-contract layer checks launch structure, not dataflow)
        return [union for _ in eqn.outvars]

    sub = _call_jaxpr(eqn.params)
    if sub is not None and len(sub.jaxpr.invars) == len(ins):
        outs = _eval_jaxpr(sub.jaxpr, ins, ctx)
        if len(outs) == len(eqn.outvars):
            return outs
    # structural default: union of inputs flows to every output
    return [union for _ in eqn.outvars]


_FIXPOINT_CAP = 32  # |labels| passes suffice; cap is a safety net


def _eval_scan(eqn, ins, ctx) -> List[frozenset]:
    p = eqn.params
    body = p["jaxpr"].jaxpr
    nc, nk = p["num_consts"], p["num_carry"]
    consts, carry, xs = list(ins[:nc]), list(ins[nc:nc + nk]), \
        list(ins[nc + nk:])
    quiet = ctx.quiet()
    for _ in range(_FIXPOINT_CAP):
        outs = _eval_jaxpr(body, consts + carry + xs, quiet)
        new_carry = [c | o for c, o in zip(carry, outs[:nk])]
        if new_carry == carry:
            break
        carry = new_carry
    outs = _eval_jaxpr(body, consts + carry + xs, ctx)
    return [c | o for c, o in zip(carry, outs[:nk])] + outs[nk:]


def _eval_while(eqn, ins, ctx) -> List[frozenset]:
    p = eqn.params
    cond, body = p["cond_jaxpr"].jaxpr, p["body_jaxpr"].jaxpr
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    cconsts = list(ins[:cn])
    bconsts = list(ins[cn:cn + bn])
    carry = list(ins[cn + bn:])
    quiet = ctx.quiet()
    for _ in range(_FIXPOINT_CAP):
        outs = _eval_jaxpr(body, bconsts + carry, quiet)
        new_carry = [c | o for c, o in zip(carry, outs)]
        if new_carry == carry:
            break
        carry = new_carry
    outs = _eval_jaxpr(body, bconsts + carry, ctx)
    carry = [c | o for c, o in zip(carry, outs)]
    # implicit flow: the loop's exit condition gates every output
    pred = _union(_eval_jaxpr(cond, cconsts + carry, ctx))
    return [c | pred for c in carry]


def _eval_cond(eqn, ins, ctx) -> List[frozenset]:
    branches = eqn.params["branches"]
    pred, ops = ins[0], ins[1:]
    per_branch = []
    for br in branches:
        if len(br.jaxpr.invars) == len(ops):
            per_branch.append(_eval_jaxpr(br.jaxpr, list(ops), ctx))
        else:  # arity surprise: fall back to full union
            per_branch.append([_union(ops)] * len(eqn.outvars))
    n_out = len(eqn.outvars)
    # branch union + predicate taint (implicit flow through selection)
    return [_union([b[i] for b in per_branch if i < len(b)]) | pred
            for i in range(n_out)]


# ---------------------------------------------------------------------------
# analysis targets
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TaintTarget:
    """One jaxpr to verify. `build()` -> (fn, args, labels): `fn` is
    traced as jax.make_jaxpr(fn)(*args); `labels` mirrors the pytree
    structure of `args` with a source-label string per leaf ("" =
    untainted) — build label trees with jax.tree.map over the args so
    the flattenings line up."""
    name: str
    build: Callable


# name -> target; populated by fixture modules at import time
TARGETS: Dict[str, TaintTarget] = {}


def taint_target(*, name: str, build: Callable) -> TaintTarget:
    """Register an analysis target (the fixture-module hook, mirroring
    `registry.kernel_contract`)."""
    t = TaintTarget(name=name, build=build)
    TARGETS[name] = t
    return t


class capture_targets:
    """Context manager: record targets registered while active (used to
    check fixture modules in isolation from head_targets)."""

    def __enter__(self) -> List[TaintTarget]:
        self._before = set(TARGETS)
        self._new: List[TaintTarget] = []
        return self._new

    def __exit__(self, *exc):
        for k in set(TARGETS) - self._before:
            self._new.append(TARGETS.pop(k))
        return False


def check_target(target: TaintTarget) -> List[Finding]:
    """Trace one target under the marker scope and run the engine."""
    import jax

    findings: List[Finding] = []
    try:
        fn, args, labels = target.build()
        with privacy.tracing():
            closed = jax.make_jaxpr(fn)(*args)
        label_leaves = jax.tree_util.tree_leaves(labels)
        in_taints = [frozenset([lab]) if lab else EMPTY
                     for lab in label_leaves]
        if len(in_taints) != len(closed.jaxpr.invars):
            return [Finding(
                "taint-trace-error", "<taint>", 0,
                f"{target.name}: {len(in_taints)} source labels for "
                f"{len(closed.jaxpr.invars)} jaxpr invars — the label "
                f"tree must mirror the args tree")]
        ctx = _Ctx(target=target.name, findings=findings)
        _eval_jaxpr(closed.jaxpr, in_taints, ctx)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        return [Finding(
            "taint-trace-error", "<taint>", 0,
            f"{target.name}: {type(e).__name__}: {e}")]
    return findings


def check_targets(targets=None) -> List[Finding]:
    targets = head_targets() if targets is None else targets
    out: List[Finding] = []
    for t in targets:
        out.extend(check_target(t))
    return out


# ---------------------------------------------------------------------------
# HEAD targets: the protocol surface, over a tiny oracle-backend fixture
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _tiny():
    """4-client MLP federation on the oracle backends: the smallest
    shapes that exercise every protocol path (N=2 neighbors, 1 local
    step) while keeping each make_jaxpr trace sub-second."""
    import functools as ft

    import jax
    import jax.numpy as jnp

    from repro.configs.paper_models import ClientModelConfig, FedConfig
    from repro.core import protocol
    from repro.models import apply_client_model, init_client_model
    from repro.optim import adam

    m, d, classes, n_loc, n_ref = 4, 8, 3, 8, 4
    mcfg = ClientModelConfig("taint-mlp", "mlp", (d,), classes,
                             hidden=(8,))
    fed = FedConfig(num_clients=m, num_neighbors=2, top_k=1,
                    local_steps=1, local_batch=4, lsh_bits=32, lr=1e-2,
                    selection_backend="oracle",
                    exchange_backend="oracle")
    apply_fn = ft.partial(apply_client_model, mcfg)

    def init_fn(k):
        return init_client_model(mcfg, k)

    opt = adam(fed.lr)
    state = protocol.init_state(apply_fn, init_fn, opt, fed,
                                jax.random.PRNGKey(0))
    data = {
        "x_train": jnp.zeros((m, n_loc, d), jnp.float32),
        "y_train": jnp.zeros((m, n_loc), jnp.int32),
        "x_ref": jnp.zeros((m, n_ref, d), jnp.float32),
        "y_ref": jnp.zeros((m, n_ref), jnp.int32),
        "x_test": jnp.zeros((m, n_loc, d), jnp.float32),
        "y_test": jnp.zeros((m, n_loc), jnp.int32),
    }
    return {"fed": fed, "apply_fn": apply_fn, "init_fn": init_fn,
            "opt": opt, "state": state, "data": data, "m": m, "d": d}


def _fed_labels(state):
    """FedState label tree: params/opt_state private, published fields
    (codes, rankings, commitments — last round's declassified
    announcements) and rng/round untainted."""
    import jax
    lab = jax.tree.map(lambda _: "", state)
    return lab._replace(
        params=jax.tree.map(lambda _: SRC_PARAMS, state.params),
        opt_state=jax.tree.map(lambda _: SRC_OPT, state.opt_state))


def _data_labels(data):
    import jax
    return jax.tree.map(lambda _: SRC_DATA, data)


def _head_target_builders():
    """name -> build() pairs for every protocol surface the verifier
    proves clean (one entry per (fn, args, labels) trace)."""
    t = _tiny()
    fed, apply_fn, opt = t["fed"], t["apply_fn"], t["opt"]
    state, data = t["state"], t["data"]

    from repro.core import adversary, baselines, protocol
    from repro.core.rounds import make_segment_fn
    from repro.service import driver as svc_driver
    from repro.service import serving
    from repro.service.membership import ServiceConfig, init_service_state

    sd = (state, data)
    sd_labels = (_fed_labels(state), _data_labels(data))

    def _phase_select():
        return (lambda st: protocol.select_phase(st, fed),
                (state,), (_fed_labels(state),))

    def _phase_exchange():
        def fn(st, d):
            sel = protocol.select_phase(st, fed)
            return protocol.exchange_phase(apply_fn, fed, st.params, d,
                                           sel)
        return fn, sd, sd_labels

    def _phase_update():
        def fn(st, d):
            import jax
            sel = protocol.select_phase(st, fed)
            exch = protocol.exchange_phase(apply_fn, fed, st.params, d,
                                           sel)
            return protocol.update_phase(apply_fn, opt, fed, st.params,
                                         st.opt_state, d, exch,
                                         jax.random.PRNGKey(1))
        return fn, sd, sd_labels

    def _phase_announce():
        def fn(st, d):
            sel = protocol.select_phase(st, fed)
            exch = protocol.exchange_phase(apply_fn, fed, st.params, d,
                                           sel)
            return protocol.announce_phase(fed, st.params, sel, exch,
                                           st.round)
        return fn, sd, sd_labels

    wpfed = protocol.wpfed_program(apply_fn, opt, fed)

    def _wpfed_global():
        return wpfed.global_round, sd, sd_labels

    def _wpfed_gossip():
        def fn(st, d):
            sel = protocol.select_phase(st, fed)
            return wpfed.gossip_round(st, d, sel)
        return fn, sd, sd_labels

    def _wpfed_segment_tap():
        seg = make_segment_fn(wpfed, 3, metrics_tap=lambda s: None)
        return seg, sd, sd_labels

    def _instrumented_global():
        tm = adversary.resolve_threat(
            "lsh_cheat", num_clients=t["m"], attacker_frac=0.25,
            init_fn=t["init_fn"], start_round=0, target_id=0)
        inst = adversary.instrument_program(wpfed, tm)
        seg = make_segment_fn(inst, 2, metrics_tap=lambda s: None)
        return seg, sd, sd_labels

    def _baseline(name):
        def build():
            import jax.numpy as jnp
            kwargs = {}
            if name == "fedmd":
                kwargs["shared_ref_x"] = jnp.zeros(
                    data["x_ref"].shape[1:], data["x_ref"].dtype)
            prog = baselines.BASELINE_PROGRAMS[name](apply_fn, opt, fed,
                                                     **kwargs)
            return prog.global_round, sd, sd_labels
        return build

    svc = ServiceConfig(reselect_every=2)
    svc_prog = svc_driver.service_program(apply_fn, opt, fed, svc)
    svc_state = init_service_state(state, svc)
    ssd = (svc_state, data)
    ssd_labels = (svc_state._replace(
        fed=_fed_labels(state),
        active="", code_age="", gossip_count="", period_start=""),
        _data_labels(data))

    def _service_global():
        return svc_prog.global_round, ssd, ssd_labels

    def _service_segment_tap():
        seg = make_segment_fn(svc_prog, 2, metrics_tap=lambda s: None)
        return seg, ssd, ssd_labels

    def _service_degraded():
        # a DEGRADED round (DESIGN.md §15): stragglers masked inactive
        # mid-service and a stale re-joiner with nonzero code_age —
        # the disclosure boundary must hold on the faulted path too
        # (the -inf masking / staleness discount are extra dataflow
        # through the Eq. 8 scores into the ledger-publish sink)
        import jax.numpy as jnp
        from repro.service.membership import mask_stragglers
        degraded = mask_stragglers(
            svc_state._replace(
                code_age=jnp.arange(t["m"], dtype=jnp.int32)),
            jnp.arange(t["m"]) == 1)
        return svc_prog.global_round, (degraded, data), ssd_labels

    def _serving_forward():
        import jax
        import jax.numpy as jnp
        ids = jnp.zeros((2,), jnp.int32)
        x = jnp.zeros((2, t["d"]), jnp.float32)
        return (functools.partial(serving._forward_fn, apply_fn),
                (state.params, ids, x),
                (jax.tree.map(lambda _: SRC_PARAMS, state.params),
                 "", ""))

    return [
        ("phase-select", _phase_select),
        ("phase-exchange", _phase_exchange),
        ("phase-update", _phase_update),
        ("phase-announce", _phase_announce),
        ("wpfed-global-round", _wpfed_global),
        ("wpfed-gossip-round", _wpfed_gossip),
        ("wpfed-segment-tapped", _wpfed_segment_tap),
        ("wpfed-instrumented-segment", _instrumented_global),
        ("baseline-silo", _baseline("silo")),
        ("baseline-fedmd", _baseline("fedmd")),
        ("baseline-proxyfl", _baseline("proxyfl")),
        ("baseline-kdpdfl", _baseline("kdpdfl")),
        ("service-global-round", _service_global),
        ("service-segment-tapped", _service_segment_tap),
        ("service-degraded-round", _service_degraded),
        ("serving-forward", _serving_forward),
    ]


def head_targets() -> List[TaintTarget]:
    return [TaintTarget(name=name, build=build)
            for name, build in _head_target_builders()]
