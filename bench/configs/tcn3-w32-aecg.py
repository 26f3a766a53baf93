"""Plain reference of the tcn3-w32-aecg client model
(tcn3-w32-aecg.json).

Residual blocks of a causal dilated 1-D convolution (kernel 5,
dilation 2**i, left padding), ReLU, plus the input (through a 1x1
projection where the width changes); global average pooling over time
and a linear head (arXiv:2410.11378 §4.3). Parameters are a dict whose
"blocks" list holds {"conv", "b", "res"} with "res" None where the
width is unchanged, the tree the system under test uses.
"""
import jax
import jax.numpy as jnp


def _normal(key, shape, dtype):
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def init(cfg, key, dtype=jnp.float32):
    m = cfg["model"]
    cin, k = m["input_shape"][-1], m["kernel_size"]
    keys = jax.random.split(key, 2 * len(m["hidden"]) + 1)
    blocks = []
    for i, ch in enumerate(m["hidden"]):
        blocks.append({
            "conv": _normal(keys[2 * i], (k, cin, ch), dtype),
            "b": jnp.zeros((ch,), dtype),
            "res": (_normal(keys[2 * i + 1], (cin, ch), dtype)
                    if cin != ch else None)})
        cin = ch
    return {"blocks": blocks,
            "fc": _normal(keys[-1], (cin, m["num_classes"]), dtype),
            "bf": jnp.zeros((m["num_classes"],), dtype)}


def apply(p, x):
    """x: (B, T, C) -> logits (B, classes)."""
    y = x
    for i, blk in enumerate(p["blocks"]):
        k, dil = blk["conv"].shape[0], 2 ** i
        yp = jnp.pad(y, ((0, 0), ((k - 1) * dil, 0), (0, 0)))
        conv = jax.lax.conv_general_dilated(
            yp, blk["conv"], (1,), "VALID", rhs_dilation=(dil,),
            dimension_numbers=("NTC", "TIO", "NTC")) + blk["b"]
        res = y @ blk["res"] if blk["res"] is not None else y
        y = jax.nn.relu(conv) + res
    return jnp.mean(y, axis=1) @ p["fc"] + p["bf"]


def forward_flops(cfg):
    """2 x the multiply-adds of one example's forward pass that touch
    real inputs (taps on the causal zero padding are not work), from
    shapes."""
    m = cfg["model"]
    t, cin = m["input_shape"]
    k, macs = m["kernel_size"], 0
    for i, ch in enumerate(m["hidden"]):
        taps = sum(sum(s - (k - 1 - j) * 2 ** i >= 0 for j in range(k))
                   for s in range(t))
        macs += taps * cin * ch + (t * cin * ch if cin != ch else 0)
        cin = ch
    return 2 * (macs + cin * m["num_classes"])
