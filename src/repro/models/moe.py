"""Mixture-of-Experts FFN with top-k routing and capacity-bounded
scatter/gather dispatch (no (T, E, C) one-hot tensors — memory-light and
all-to-all-friendly under expert sharding).

Dispatch algorithm (per call, T = flattened tokens):
  1. router logits (T, E) -> softmax -> top-k expert ids + weights.
  2. position-in-expert via SORT over the (T*k,) expert assignments
     (O(Tk log Tk)); the textbook (T*k, E) one-hot cumsum is O(Tk*E)
     compute AND lowers to a size-Tk reduce-window in XLA — measured
     481x the useful MoE FLOPs at kimi-k2 scale (EXPERIMENTS.md §Perf
     iteration 1).
  3. tokens scattered into an (E*C, D) buffer (capacity C drops overflow),
     expert FFNs run batched over E, outputs gathered back and combined
     with router weights.

Sharding: expert-major params (E, D, F). For E >= 16 the expert axis is
sharded on the mesh "model" axis (expert parallelism; XLA inserts the
all-to-all-equivalent collectives at the scatter/gather); for small E the
FFN width is sharded instead (tensor parallelism inside each expert).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import GATED, _act, dense_init, split_keys

EXPERT_SHARD_MIN = 16

# Dispatch distribution knobs, set by the launcher (tests/CPU leave the
# defaults). Two measured pathologies motivate them (EXPERIMENTS.md
# §Perf iterations 2-3):
#   * without a buffer constraint the SPMD partitioner shards the
#     dispatch buffer on E only, so every data-axis device REPLICATES
#     the expert matmuls (16x redundant compute at kimi-k2 scale);
#   * with a single global dispatch, tokens scatter across data shards
#     and XLA all-gathers the whole (T*k, D) update tensor (~120 GB/dev
#     at kimi train_4k). Grouped dispatch (_NUM_GROUPS = data shards)
#     keeps the scatter group-local; the only cross-device traffic left
#     is the genuine expert-parallel exchange over the model axis.
_DISPATCH_SPEC = None      # PartitionSpec for the (G, E, C, D) buffer
_NUM_GROUPS = 1


def set_dispatch_spec(spec, num_groups: int = 1):
    global _DISPATCH_SPEC, _NUM_GROUPS
    _DISPATCH_SPEC = spec
    _NUM_GROUPS = max(int(num_groups), 1)


def default_dispatch_spec(cfg: ModelConfig, batch_axes):
    e_axis = "model" if cfg.num_experts >= EXPERT_SHARD_MIN else None
    return P(batch_axes, e_axis, None, None)


def _constrain(x):
    if _DISPATCH_SPEC is None:
        return x
    spec = _DISPATCH_SPEC
    if x.shape[0] == 1:               # grouping fell back to G=1
        spec = P(None, *list(spec)[1:])
    return jax.lax.with_sharding_constraint(x, spec)


def init_moe(cfg: ModelConfig, key, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = split_keys(key, 4)
    p = {"router": dense_init(ks[0], (d, e), dtype, scale=d ** -0.5)}
    if cfg.activation in GATED:
        p["wg"] = dense_init(ks[1], (e, d, f), dtype)
    p["wi"] = dense_init(ks[2], (e, d, f), dtype)
    p["wo"] = dense_init(ks[3], (e, f, d), dtype)
    return p


def moe_specs(cfg: ModelConfig):
    if cfg.num_experts >= EXPERT_SHARD_MIN:
        up, down = P("model", None, None), P("model", None, None)
    else:
        up, down = P(None, None, "model"), P(None, "model", None)
    p = {"router": P(None, None), "wi": up, "wo": down}
    if cfg.activation in GATED:
        p["wg"] = up
    return p


def _position_in_expert(flat_e):
    """Rank of each slot within its expert group, via stable sort.

    sort by expert id -> group positions are index minus group start
    (cummax of group-start indices) -> undo the permutation.
    """
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)              # (N,)
    sorted_e = jnp.take(flat_e, order)
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_sorted = idx - group_start
    inv = jnp.argsort(order, stable=True)                 # undo permutation
    return jnp.take(pos_sorted, inv)


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(num_tokens * cfg.experts_per_token * cfg.moe_capacity_factor
            / cfg.num_experts)
    return max(c, cfg.experts_per_token)


def _dispatch_ffn(cfg: ModelConfig, p, xt, e_ids, cap):
    """Capacity-bounded dispatch + expert FFN + combine for ONE group.

    xt: (T, D) tokens; e_ids context: experts are p["wi"].shape[0] (may
    be a LOCAL shard under shard_map). Returns (T, D) combined output
    and keep mask. Tokens routed to experts outside [0, E_here) are
    masked out (shard_map path: other ranks own them)."""
    t, d = xt.shape
    e_here = p["wi"].shape[0]
    k = e_ids.shape[-1]
    flat_e = e_ids.reshape(-1)
    here = (flat_e >= 0) & (flat_e < e_here)
    flat_pos = _position_in_expert(jnp.where(here, flat_e, e_here))
    keep = here & (flat_pos < cap)
    dest = jnp.where(keep, flat_e * cap + flat_pos, e_here * cap)

    src = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e_here * cap + 1, d), xt.dtype).at[dest].add(
        xt[src] * keep[:, None].astype(xt.dtype))
    buf = buf[:-1].reshape(e_here, cap, d)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    if cfg.activation in GATED:
        gate = _act(GATED[cfg.activation],
                    jnp.einsum("ecd,edf->ecf", buf, p["wg"]))
        h = gate * h
    else:
        h = _act(cfg.activation, h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(e_here * cap, d)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((1, d), out_buf.dtype)])
    return out_buf, dest, keep, src


def apply_moe(cfg: ModelConfig, p, x):
    """x: (B, S, D) -> (B, S, D), plus aux dict (load-balance stats).

    Dispatch runs in ``G = _NUM_GROUPS`` independent groups (the
    launcher sets G to the data-shard count so each group's
    scatter/gather stays device-local; G=1 reproduces the global
    textbook dispatch — capacity is per-group either way).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    g = _NUM_GROUPS if t % _NUM_GROUPS == 0 else 1
    tg = t // g
    xt = x.reshape(g, tg, d)

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                  # (G,Tg,k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    cap = _capacity(cfg, tg)
    flat_e = topi.reshape(g, tg * k)                      # (G,Tg*k)
    flat_pos = jax.vmap(_position_in_expert)(flat_e)
    keep = flat_pos < cap
    dest = flat_e * cap + flat_pos
    dest = jnp.where(keep, dest, e * cap)                 # overflow slot

    src = jnp.repeat(jnp.arange(tg), k)                   # token idx per slot
    gi = jnp.arange(g)[:, None]
    buf = jnp.zeros((g, e * cap + 1, d), x.dtype).at[gi, dest].add(
        xt[:, src] * keep[..., None].astype(x.dtype))
    buf = _constrain(buf[:, :-1].reshape(g, e, cap, d))

    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"])
    if cfg.activation in GATED:
        gate = _act(GATED[cfg.activation],
                    jnp.einsum("gecd,edf->gecf", buf, p["wg"]))
        h = gate * h
    else:
        h = _act(cfg.activation, h)
    out_buf = _constrain(jnp.einsum("gecf,efd->gecd", h, p["wo"]))
    out_buf = out_buf.reshape(g, e * cap, d)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((g, 1, d), out_buf.dtype)], axis=1)

    gathered = out_buf[gi, dest] * (
        topw.reshape(g, -1, 1).astype(out_buf.dtype)
        * keep[..., None].astype(out_buf.dtype))
    out = jnp.zeros((g, tg, d), out_buf.dtype).at[gi, src].add(gathered)

    # load-balance aux loss terms (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                     # router prob mass
    ce = jnp.mean(jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1))
    aux = {"load_balance": e * jnp.sum(me * ce),
           "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return out.reshape(b, s, d), aux


# ===========================================================================
# shard_map implementation (§Perf iteration 4 — beyond-paper)
# ===========================================================================
# XLA's SPMD partitioner cannot prove locality of the data-dependent
# dispatch scatter, so at jit level it either replicates expert compute
# (no constraint), or all-reduces the full dispatch buffer (constrained;
# measured 5342 s collective at kimi train_4k). shard_map makes the
# schedule explicit: tokens are replicated within a model-axis row; each
# model rank dispatches ONLY to the experts it owns (E-sharded, E >= 16)
# or runs every expert's FFN shard (F-sharded, E < 16); a single psum
# over "model" combines outputs — identical collective shape to a
# tensor-parallel MLP all-reduce.
_SHARDED = None


def set_sharded_impl(mesh=None, *, batch_axes=("data",)):
    """Enable (mesh given) or disable (None) the shard_map MoE path."""
    global _SHARDED
    _SHARDED = None if mesh is None else {"mesh": mesh,
                                          "batch_axes": tuple(batch_axes)}


def moe_forward(cfg: ModelConfig, p, x):
    """Entry point used by the transformer blocks."""
    if _SHARDED is not None:
        return apply_moe_sharded(cfg, p, x)
    return apply_moe(cfg, p, x)


def apply_moe_sharded(cfg: ModelConfig, p, x):
    mesh = _SHARDED["mesh"]
    baxes = _SHARDED["batch_axes"]
    e, k = cfg.num_experts, cfg.experts_per_token
    e_sharded = e >= EXPERT_SHARD_MIN
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    up = P("model", None, None) if e_sharded else P(None, None, "model")
    down = P("model", None, None) if e_sharded else P(None, "model", None)
    wspec = {"router": P(None, None), "wi": up, "wo": down}
    if cfg.activation in GATED:
        wspec["wg"] = up
    xspec = P(baxes, None, None)
    all_axes = tuple(a for a in mesh.axis_names)

    def body(p_l, x_l):
        b, s, d = x_l.shape
        t = b * s
        xt = x_l.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p_l["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)              # (T,k) global ids
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        e_here = p_l["wi"].shape[0]                       # local expert count
        if e_sharded:
            r = jax.lax.axis_index("model")
            local_ids = topi - r * e_here                 # out-of-range ->
        else:                                             # masked in dispatch
            local_ids = topi
        cap = _capacity(cfg, t)
        out_buf, dest, keep, src = _dispatch_ffn(cfg, p_l, xt,
                                                 local_ids, cap)
        gathered = out_buf[dest] * (
            topw.reshape(-1, 1).astype(out_buf.dtype)
            * keep[:, None].astype(out_buf.dtype))
        out = jnp.zeros((t, d), out_buf.dtype).at[src].add(gathered)
        out = jax.lax.psum(out, "model")                  # the ONE collective

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32),
                      axis=0)
        lb = e * jnp.sum(me * ce)
        kept = jnp.sum(keep.astype(jnp.float32))
        slots = jnp.float32(t * k) / (n_model if e_sharded else 1)
        aux = {"load_balance": jax.lax.pmean(lb, all_axes),
               "dropped_frac": 1.0 - jax.lax.pmean(kept, all_axes)
               / slots}
        return out.reshape(b, s, d).astype(x_l.dtype), aux

    return jax.shard_map(
        body, mesh=mesh, in_specs=(wspec, xspec),
        out_specs=(xspec, {"load_balance": P(), "dropped_frac": P()}),
        check_vma=False)(p, x)
