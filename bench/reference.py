"""Plain reference of the WPFed federation (arXiv:2410.11378, Alg. 1),
written from the paper and the configuration alone: it imports nothing
of the system under test.

One global round: §3.6 commit check of last round's revealed rankings,
Eq. 7 ranking scores, Eq. 5-8 LSH-weighted top-N partner selection,
the reference-set exchange (Eq. 3 losses l_ij, the §3.5 lower-half KL
filter and the distillation target), `local_steps` Adam steps per
client on alpha * CE + (1 - alpha) * distillation MSE, new LSH codes
with the next round's seed, rankings and FNV-1a commitments, and the
mean test accuracy. A gossip epoch re-runs exchange and update against
the period's selection. Clients run one at a time under lax.map, so
the reference fits beside the inputs at the cells' sizes.

`dtype` float32 runs under matmul precision "highest"; bfloat16 casts
weights, the shared weights, floating inputs and optimizer moments
down and is the control that the comparison has to refuse. Integer
inputs (labels, token ids) are never cast. Where the data hold
"shared", the configuration's frozen weights that all clients share,
the model is applied as `apply(p, x, shared)`. `fault` plants one of
the faults the comparison has to catch ("half_batch": the local loss
averages over the first half of each minibatch).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_K1, _K2, _K3 = 2654435761, 40503, 2246822519
_CHUNK = 16384
B1, B2, EPS = 0.9, 0.999, 1e-8


class State(NamedTuple):
    params: dict
    opt: dict
    codes: jnp.ndarray
    rankings: jnp.ndarray
    commitments: jnp.ndarray
    rng: jnp.ndarray
    round: jnp.ndarray


def cast_floating(tree, dtype):
    """The tree with its floating leaves in `dtype`; integer leaves,
    and leaves already in `dtype`, as they are."""
    return jax.tree.map(
        lambda v: v.astype(dtype) if (jnp.issubdtype(v.dtype, jnp.floating)
                                      and v.dtype != dtype) else v, tree)


# --------------------------------------------------------------- hashing
def rademacher(i0, n, bits, seed):
    """+-1 entries R[i0:i0+n, :bits] of the shared per-round projection:
    an integer hash of (row, bit, seed)."""
    i = (jnp.asarray(i0, jnp.uint32)
         + jnp.arange(n, dtype=jnp.uint32))[:, None]
    j = jnp.arange(bits, dtype=jnp.uint32)[None, :]
    s = jnp.asarray(seed).astype(jnp.uint32)
    h = (i * jnp.uint32(_K1)) ^ (j * jnp.uint32(_K2) + s * jnp.uint32(_K3))
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(_K3)
    h = h ^ (h >> jnp.uint32(13))
    return jnp.where(((h >> jnp.uint32(9)) & jnp.uint32(1)) != 0, -1.0, 1.0)


def lsh_codes(params, seed, bits):
    """Eq. 5: signs of the projection of each client's flattened
    parameters, packed 32 to a uint32 word, lowest bit first."""
    flat = jnp.concatenate([x.reshape(x.shape[0], -1)
                            for x in jax.tree.leaves(params)], axis=1)
    m, p = flat.shape
    pad = (-p) % _CHUNK
    chunks = jnp.pad(flat, ((0, 0), (0, pad))).reshape(
        m, (p + pad) // _CHUNK, _CHUNK).transpose(1, 0, 2)

    def add(acc, xs):
        c, x = xs
        r = rademacher(c * _CHUNK, _CHUNK, bits, seed).astype(x.dtype)
        return acc + jnp.dot(x, r, preferred_element_type=jnp.float32), None

    sums, _ = jax.lax.scan(add, jnp.zeros((m, bits), jnp.float32),
                           (jnp.arange(chunks.shape[0]), chunks))
    on = (sums > 0).astype(jnp.uint32).reshape(m, bits // 32, 32)
    return jnp.sum(on << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def fnv1a(rankings):
    """FNV-1a (32-bit) over the four little-endian bytes of each id."""
    r = rankings.astype(jnp.uint32)
    h = jnp.full(r.shape[:-1], 2166136261, jnp.uint32)
    for idx in range(r.shape[-1]):
        for shift in (0, 8, 16, 24):
            byte = (r[..., idx] >> jnp.uint32(shift)) & jnp.uint32(0xFF)
            h = (h ^ byte) * jnp.uint32(16777619)
    return h


# ------------------------------------------------------------- selection
def ranking_scores(rankings, top_k, dedupe):
    """Eq. 7: s_j = #(rankings with j in their top K) / #(rankings
    holding j). With `dedupe` a ranking equal to an earlier one is
    not counted again."""
    m = rankings.shape[0]
    count = jnp.ones((m,), bool)
    if dedupe:
        same = jnp.all(rankings[:, None] == rankings[None], axis=-1)
        earlier = jnp.arange(m)[None, :] < jnp.arange(m)[:, None]
        count = ~jnp.any(same & earlier, axis=1)
    hit = (rankings[..., None] == jnp.arange(m)) & (rankings[..., None] >= 0)
    hit = hit & count[:, None, None]
    appears = jnp.sum(hit, axis=(0, 1)).astype(jnp.float32)
    in_top = jnp.sum(hit[:, :top_k], axis=(0, 1)).astype(jnp.float32)
    return in_top / jnp.maximum(appears, 1.0)


def select(codes, scores, n, gamma, bits):
    """Eq. 6-8: Hamming distance d_ij of the codes, weight
    s_j * exp(-gamma d_ij / bits), top N other clients (ties to the
    lower id)."""
    m = codes.shape[0]
    x = codes[:, None, :] ^ codes[None, :, :]
    d = jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.float32)
    w = scores[None, :] * jnp.exp(-gamma * (d / float(bits)))
    w = jnp.where(jnp.eye(m, dtype=bool), -jnp.inf, w)
    top_w, ids = jax.lax.top_k(w, n)
    return ids.astype(jnp.int32), jnp.isfinite(top_w)


# -------------------------------------------------------------- exchange
def exchange(apply, params, x_ref, y_ref, ids, sel_mask, public):
    """Reference-set logits of each client and of its selected
    neighbors, Eq. 3 l_ij, the §3.5 mask and the distillation target."""
    m = ids.shape[0]
    if public:
        own = jax.lax.map(lambda p: apply(p, x_ref[0]), params)
        nb = own[ids]
        y = jnp.broadcast_to(y_ref[0], (m,) + y_ref.shape[1:])
    else:
        own = jax.lax.map(lambda a: apply(a[0], a[1]), (params, x_ref))
        nb = jax.lax.map(
            lambda a: jax.vmap(apply, in_axes=(0, None))(
                jax.tree.map(lambda q: q[a[0]], params), a[1]),
            (ids, x_ref))
        y = y_ref
    logp_nb = jax.nn.log_softmax(nb, axis=-1)
    pick = jnp.take_along_axis(
        logp_nb, jnp.broadcast_to(y[:, None, :, None],
                                  logp_nb.shape[:-1] + (1,)), axis=-1)
    l_ij = -jnp.mean(pick[..., 0], axis=-1)
    logp_own = jax.nn.log_softmax(own, axis=-1)
    kl = jnp.mean(jnp.sum(jnp.exp(logp_own)[:, None]
                          * (logp_own[:, None] - logp_nb), axis=-1), axis=-1)
    kl = jnp.where(sel_mask, kl, jnp.inf)
    keep = (jnp.sum(sel_mask, axis=-1, keepdims=True) + 1) // 2
    k = jnp.arange(kl.shape[1])
    before = (kl[:, :, None] < kl[:, None, :]) | (
        (kl[:, :, None] == kl[:, None, :]) & (k[:, None] < k[None, :]))
    valid = (jnp.sum(before, axis=1) < keep) & sel_mask
    w = valid.astype(nb.dtype)
    target = (jnp.einsum("mn,mnrc->mrc", w, nb)
              / jnp.maximum(jnp.sum(w, axis=-1), 1)[:, None, None])
    return l_ij, valid, target, jnp.any(valid, axis=-1)


def make_ranking(ids, l_ij, sel_mask):
    order = jnp.argsort(jnp.where(sel_mask, l_ij, jnp.inf), axis=-1,
                        stable=True)
    ok = jnp.take_along_axis(sel_mask, order, axis=-1)
    return jnp.where(ok, jnp.take_along_axis(ids, order, axis=-1), -1)


# ---------------------------------------------------------------- update
def _cross_entropy(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def update(apply, fed, params, opt, data, x_ref, target, has_target,
           rng, fault=None):
    """`local_steps` Adam steps per client on alpha * CE(local batch)
    + (1 - alpha) * mean((f(x_ref) - target)^2), x_ref (M, R, ...)
    holding each client's reference rows; returns the loss of each
    client's last step."""
    m = target.shape[0]
    n_local = data["x_train"].shape[1]
    mb = min(fed["local_batch"], n_local)
    alpha, lr = fed["alpha"], fed["lr"]

    def client(a):
        p, o, x_tr, y_tr, xr, t, has, key = a

        def loss_fn(q, xb, yb):
            if fault == "half_batch":
                xb, yb = xb[:mb // 2], yb[:mb // 2]
            l_loc = _cross_entropy(apply(q, xb), yb)
            l_ref = jnp.mean(jnp.square(apply(q, xr) - t))
            return alpha * l_loc + (1 - alpha) * jnp.where(has, l_ref, 0)

        def step(carry, k):
            q, o = carry
            idx = jax.random.randint(k, (mb,), 0, n_local)
            loss, g = jax.value_and_grad(loss_fn)(q, x_tr[idx], y_tr[idx])
            t_ = o["step"] + 1
            mo = jax.tree.map(lambda a_, b: B1 * a_ + (1 - B1) * b,
                              o["m"], g)
            vo = jax.tree.map(lambda a_, b: B2 * a_ + (1 - B2) * b * b,
                              o["v"], g)
            c1 = 1 - B1 ** t_.astype(jnp.float32)
            c2 = 1 - B2 ** t_.astype(jnp.float32)
            q = jax.tree.map(
                lambda w_, a_, b: w_ - (lr * (a_ / c1)
                                        / (jnp.sqrt(b / c2) + EPS)
                                        ).astype(w_.dtype), q, mo, vo)
            return (q, {"step": t_, "m": mo, "v": vo}), loss

        keys = jax.random.split(key, fed["local_steps"])
        (p, o), losses = jax.lax.scan(step, (p, o), keys)
        return p, o, losses[-1]

    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(m))
    return jax.lax.map(client, (params, opt, data["x_train"],
                                data["y_train"], x_ref, target, has_target,
                                keys))


def accuracy(apply, params, data):
    acc = jax.lax.map(
        lambda a: jnp.mean((jnp.argmax(apply(a[0], a[1]), -1) == a[2])
                           .astype(jnp.float32)),
        (params, data["x_test"], data["y_test"]))
    return jnp.mean(acc)


# ---------------------------------------------------------------- rounds
class Federation:
    """The reference federation of one cell: `init(key)` and the two
    kinds of round, each one compiled program."""

    def __init__(self, model, cfg, clients, public, dtype=jnp.float32,
                 fault=None):
        self.model, self.cfg, self.fed = model, cfg, cfg["fed"]
        self.m, self.public, self.dtype = clients, public, dtype
        self.n = min(self.fed["num_neighbors"], clients - 1)
        self.fault = fault
        self.global_round = self._compiled(self._global)
        self.gossip_round = self._compiled(self._gossip)

    def _compiled(self, fn):
        jitted = jax.jit(fn)
        if self.dtype != jnp.float32:
            return jitted

        def run(*args):
            with jax.default_matmul_precision("highest"):
                return jitted(*args)
        return run

    def apply(self, p, x, shared=None):
        x = cast_floating(x, self.dtype)
        if shared is None:
            return self.model.apply(p, x)
        return self.model.apply(p, x, shared)

    def _applier(self, data):
        """`apply(p, x)` with the data's shared weights, if any."""
        return functools.partial(self.apply, shared=data.get("shared"))

    def init(self, key):
        keys = jax.random.split(key, self.m)
        params = jax.vmap(lambda k: self.model.init(self.cfg, k))(keys)
        params = jax.tree.map(lambda a: a.astype(self.dtype), params)
        zeros = lambda a: jnp.zeros(a.shape, self.dtype)
        opt = {"step": jnp.zeros((self.m,), jnp.int32),
               "m": jax.tree.map(zeros, params),
               "v": jax.tree.map(zeros, params)}
        with jax.default_matmul_precision("highest"):
            codes = jax.jit(lsh_codes, static_argnums=2)(
                params, 0, self.fed["lsh_bits"])
        rankings = -jnp.ones((self.m, self.n), jnp.int32)
        return State(params, opt, codes, rankings, fnv1a(rankings),
                     jax.random.fold_in(key, 1), jnp.zeros((), jnp.int32))

    def _exchange_update(self, st, data, ids, sel_mask, rng_upd):
        apply = self._applier(data)
        l_ij, valid, target, has = exchange(
            apply, st.params, data["x_ref"], data["y_ref"], ids,
            sel_mask, self.public)
        x_ref = data["x_ref"]
        if self.public:              # every client distills on row 0
            x_ref = jnp.broadcast_to(x_ref[0], x_ref.shape)
        params, opt, loss = update(
            apply, self.fed, st.params, st.opt, data, x_ref, target,
            has, rng_upd, self.fault)
        return l_ij, params, opt, loss

    def _global(self, st, data):
        rng, _rng_sel, rng_upd = jax.random.split(st.rng, 3)
        honest = fnv1a(st.rankings) == st.commitments
        scores = ranking_scores(
            jnp.where(honest[:, None], st.rankings, -1),
            self.fed["top_k"], dedupe=self.public)
        ids, sel_mask = select(st.codes, scores, self.n,
                               self.fed["gamma"], self.fed["lsh_bits"])
        l_ij, params, opt, loss = self._exchange_update(
            st, data, ids, sel_mask, rng_upd)
        codes = lsh_codes(params, st.round + 1, self.fed["lsh_bits"])
        rankings = make_ranking(ids, l_ij, sel_mask)
        new = State(params, opt, codes, rankings, fnv1a(rankings), rng,
                    st.round + 1)
        out = {"loss": jnp.mean(loss.astype(jnp.float32)),
               "acc": accuracy(self._applier(data), params, data)}
        return new, (ids, sel_mask), out

    def _gossip(self, st, data, sel):
        rng, rng_upd = jax.random.split(st.rng)
        _, params, opt, loss = self._exchange_update(
            st, data, sel[0], sel[1], rng_upd)
        new = st._replace(params=params, opt=opt, rng=rng,
                          round=st.round + 1)
        out = {"loss": jnp.mean(loss.astype(jnp.float32)),
               "acc": accuracy(self._applier(data), params, data)}
        return new, sel, out

    def run(self, key, data, periods, length):
        """`periods` reselection periods of `length` rounds from the
        seed's initial state. Returns the initial state, the state after
        each period and each round's loss and accuracy."""
        data = cast_floating(data, self.dtype)
        st = self.init(key)
        states, rounds = [st], []
        for _ in range(periods):
            st, sel, out = self.global_round(st, data)
            rounds.append(out)
            for _ in range(length - 1):
                st, sel, out = self.gossip_round(st, data, sel)
                rounds.append(out)
            states.append(st)
        return states, [{k: float(v) for k, v in r.items()} for r in rounds]
