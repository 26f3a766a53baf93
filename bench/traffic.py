"""The one generator of federation data: a workload file's parameters
and a seed in, the stacked per-client arrays out, made on the device
in one jitted call.

Each class has a random template; a row is its class template plus
Gaussian noise. Client i sees `classes_per_client` of the classes in
its training and test rows (label skew), and belongs to cluster
i % label_clusters, whose labels are shifted by cluster *
(classes // label_clusters) mod classes: clients of different
clusters disagree on what a class is called, so choosing neighbors
matters. Reference rows cover all classes with the client's own
labels. Under ref_mode "public" the program reads row 0 of x_ref and
y_ref as the shared set. The same seed gives the same arrays; another
seed gives other values of the same sizes.

Where the model block says "input": "tokens" with "vocab": V, a
class's template is a vector of random logits over the V ids instead,
and each id of a row is drawn on its own from the softmax of its
class's logits at temperature `noise`. That is plumbing for ids, not
a deployment's text: no order within a row, no Zipf-like frequency of
ids. A cell whose layers depend on token statistics, such as expert
routing, cites the source of its id distribution, or states that
those layers are not measured.
"""
import functools

import jax
import jax.numpy as jnp

def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits
    seed it and the rest is folded in."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


def _tokens(key, cdf, y, shape):
    """Ids of shape y.shape + shape, each drawn by its row's class from
    `cdf` (classes, V), that class's cumulative distribution: the least
    id whose cumulative share exceeds a uniform draw."""
    u = jax.random.uniform(key, y.shape + shape)
    ids = jax.vmap(lambda c: jnp.searchsorted(c, u, side="right"))(cdf)
    cls = y.reshape((1,) + y.shape + (1,) * len(shape))
    ids = jnp.take_along_axis(ids, cls, axis=0)[0]
    # a draw at or above a total that rounded under 1 takes the last id
    return jnp.minimum(ids, cdf.shape[1] - 1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("shape", "classes", "vocab", "wl"))
def _generate(key, *, shape, classes, vocab, wl):
    wl = dict(wl)
    m, k_cls = wl["clients"], wl["classes_per_client"]
    clusters, noise = wl["label_clusters"], wl["noise"]
    kt, kc, kd = jax.random.split(key, 3)
    if vocab is None:
        templates = jax.random.normal(kt, (classes,) + shape)
    else:
        logits = jax.random.normal(kt, (classes, vocab))
        cdf = jnp.cumsum(jax.nn.softmax(logits / noise, axis=-1), axis=-1)
    # each client's classes: the first k of a random permutation
    perm = jax.vmap(lambda k: jax.random.permutation(k, classes))(
        jax.random.split(kc, m))
    seen = perm[:, :k_cls]                                   # (M, k)
    shift = (jnp.arange(m) % clusters) * (classes // clusters)

    def rows(key, n, pool):
        ky, kx = jax.random.split(key)
        pick = jax.random.randint(ky, (m, n), 0, pool.shape[1])
        y = jnp.take_along_axis(pool, pick, axis=1)          # (M, n)
        if vocab is None:
            x = templates[y] + noise * jax.random.normal(
                kx, (m, n) + shape)
        else:
            x = _tokens(kx, cdf, y, shape)
        return x, ((y + shift[:, None]) % classes).astype(jnp.int32)

    k1, k2, k3 = jax.random.split(kd, 3)
    every = jnp.broadcast_to(jnp.arange(classes), (m, classes))
    x_tr, y_tr = rows(k1, wl["train_rows"], seen)
    x_te, y_te = rows(k2, wl["test_rows"], seen)
    x_rf, y_rf = rows(k3, wl["ref_rows"], every)
    return {"x_train": x_tr, "y_train": y_tr, "x_test": x_te,
            "y_test": y_te, "x_ref": x_rf, "y_ref": y_rf}


GEN_KEYS = ("clients", "classes_per_client", "label_clusters", "noise",
            "train_rows", "test_rows", "ref_rows")


def generate(cfg: dict, wl: dict, seed: int) -> dict:
    """Stacked (M, rows, ...) inputs, float32 rows or int32 ids, and
    int32 labels."""
    model = cfg["model"]
    kind = model.get("input", "features")
    if kind not in ("features", "tokens"):
        raise ValueError(f"unknown model input {kind!r}: "
                         "'features' or 'tokens'")
    return _generate(seed_key(seed), shape=tuple(model["input_shape"]),
                     classes=model["num_classes"],
                     vocab=model["vocab"] if kind == "tokens" else None,
                     wl=tuple((k, wl[k]) for k in GEN_KEYS))
