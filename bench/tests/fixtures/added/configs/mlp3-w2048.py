"""Plain reference of the fixture mlp3-w2048 (mlp3-w2048.json): the
program's "mlp" client written out, so the two compute the same
function.

The input is flattened, then each hidden layer is a dense layer with
bias and ReLU, and a last dense layer gives the logits. Parameters are
{"w": [...], "b": [...]}, one entry a layer, the tree the program's
mlp client reads.
"""
import math

import jax
import jax.numpy as jnp


def dims(cfg):
    m = cfg["model"]
    return (math.prod(m["input_shape"]), *m["hidden"], m["num_classes"])


def init(cfg, key, dtype=jnp.float32):
    """Fan-in scaled normal weights, zero biases."""
    d = dims(cfg)
    keys = jax.random.split(key, len(d) - 1)
    return {"w": [(jax.random.normal(k, (a, b), jnp.float32)
                   * a ** -0.5).astype(dtype)
                  for k, a, b in zip(keys, d[:-1], d[1:])],
            "b": [jnp.zeros((b,), dtype) for b in d[1:]]}


def apply(p, x):
    """x: (B, *input_shape) -> logits (B, classes)."""
    y = x.reshape(x.shape[0], -1)
    for i, (w, b) in enumerate(zip(p["w"], p["b"])):
        y = y @ w + b
        if i < len(p["w"]) - 1:
            y = jax.nn.relu(y)
    return y


def forward_flops(cfg):
    """2 x the multiply-adds of one example's forward pass."""
    d = dims(cfg)
    return 2 * sum(a * b for a, b in zip(d[:-1], d[1:]))


def layer_work(cfg, wl):
    """{"dense": (FLOPs, bytes)} of one period's local update: each
    client's steps over its batch and reference rows, forward and
    backward at 3 forward costs; each step reads every float32 weight
    three times (forward, backward, update)."""
    fed = cfg["fed"]
    steps = wl["clients"] * fed["local_steps"] * wl["reselect_every"]
    rows = min(fed["local_batch"], wl["train_rows"]) + wl["ref_rows"]
    return {"dense": (steps * rows * 3 * forward_flops(cfg),
                      steps * 3 * 4 * cfg["params"])}
