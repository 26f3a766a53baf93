"""Plain reference of the token client of the harness's own tests
(tokens-v97.json).

Ids (B, T) look up rows of a frozen embedding table (vocab, E) that
all clients share, the rows are averaged over positions, and a linear
head, the only tree a client trains, gives the logits. E equals T, so
the head has the shape of the program's one-layer "mlp" client, which
reads the ids themselves as features and never the table: the program
and this reference compute different functions, and the fixture shows
that the harness carries token inputs and shared weights to both, not
that they agree.
"""
import jax
import jax.numpy as jnp


def init(cfg, key, dtype=jnp.float32):
    m = cfg["model"]
    e, c = m["embed_dim"], m["num_classes"]
    w = jax.random.normal(key, (e, c), jnp.float32) * e ** -0.5
    return {"w": [w.astype(dtype)], "b": [jnp.zeros((c,), dtype)]}


def init_shared(cfg, key):
    m = cfg["model"]
    return {"embed": jax.random.normal(key, (m["vocab"], m["embed_dim"]))}


def apply(p, x, shared):
    """x: (B, T) int32 ids -> logits (B, classes)."""
    h = jnp.mean(shared["embed"][x], axis=1)
    return h @ p["w"][0] + p["b"][0]


def forward_flops(cfg):
    """The mean's adds over T rows of width E, and 2 x the head's
    multiply-adds."""
    m = cfg["model"]
    t, = m["input_shape"]
    e, c = m["embed_dim"], m["num_classes"]
    return t * e + 2 * e * c


def train_flops(cfg):
    """The forward and the head's weight gradient: nothing flows back
    into the frozen table, so the backward pass is the head's alone."""
    m = cfg["model"]
    return forward_flops(cfg) + 2 * m["embed_dim"] * m["num_classes"]
