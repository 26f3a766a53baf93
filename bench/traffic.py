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
"""
import functools

import jax
import jax.numpy as jnp

def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits
    seed it and the rest is folded in."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("shape", "classes", "wl"))
def _generate(key, *, shape, classes, wl):
    wl = dict(wl)
    m, k_cls = wl["clients"], wl["classes_per_client"]
    clusters, noise = wl["label_clusters"], wl["noise"]
    kt, kc, kd = jax.random.split(key, 3)
    templates = jax.random.normal(kt, (classes,) + shape)
    # each client's classes: the first k of a random permutation
    perm = jax.vmap(lambda k: jax.random.permutation(k, classes))(
        jax.random.split(kc, m))
    seen = perm[:, :k_cls]                                   # (M, k)
    shift = (jnp.arange(m) % clusters) * (classes // clusters)

    def rows(key, n, pool):
        ky, kx = jax.random.split(key)
        pick = jax.random.randint(ky, (m, n), 0, pool.shape[1])
        y = jnp.take_along_axis(pool, pick, axis=1)          # (M, n)
        x = templates[y] + noise * jax.random.normal(
            kx, (m, n) + shape)
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
    """Stacked (M, rows, ...) float32 inputs and int32 labels."""
    model = cfg["model"]
    return _generate(seed_key(seed), shape=tuple(model["input_shape"]),
                     classes=model["num_classes"],
                     wl=tuple((k, wl[k]) for k in GEN_KEYS))
