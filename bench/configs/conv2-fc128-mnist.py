"""Plain reference of the conv2-fc128-mnist client model
(conv2-fc128-mnist.json).

Two stride-2 3x3 convolutions (32, 64 channels, SAME padding, ReLU),
then fc 3136 -> 128 -> 10 with ReLU between. It stands in for the
MNIST client of arXiv:2410.11378 §4.3, a CNN adapted from MobileNetV2
whose widths the paper does not give: full convolutions in place of
MobileNetV2's depthwise-separable stages, widths of its own.
Parameters are a dict of named arrays, so the same tree feeds the
system and this reference.
"""
import jax
import jax.numpy as jnp

KEYS = ("conv1", "b1", "conv2", "b2", "fc1", "bf1", "fc2", "bf2")


def shapes(cfg):
    m = cfg["model"]
    (h, w, cin), classes = m["input_shape"], m["num_classes"]
    c1, c2 = m["hidden"]
    k, fc = m["kernel_size"], m["fc_width"]
    flat = (h // 4) * (w // 4) * c2
    return {"conv1": (k, k, cin, c1), "b1": (c1,),
            "conv2": (k, k, c1, c2), "b2": (c2,),
            "fc1": (flat, fc), "bf1": (fc,),
            "fc2": (fc, classes), "bf2": (classes,)}


def init(cfg, key, dtype=jnp.float32):
    """Fan-in scaled normal weights, zero biases."""
    out = {}
    keys = jax.random.split(key, len(KEYS))
    for k, (name, shape) in zip(keys, shapes(cfg).items()):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * fan_in ** -0.5).astype(dtype)
    return out


def apply(p, x):
    """x: (B, H, W, C) -> logits (B, classes)."""
    dn = ("NHWC", "HWIO", "NHWC")
    y = jax.lax.conv_general_dilated(x, p["conv1"], (2, 2), "SAME",
                                     dimension_numbers=dn) + p["b1"]
    y = jax.nn.relu(y)
    y = jax.lax.conv_general_dilated(y, p["conv2"], (2, 2), "SAME",
                                     dimension_numbers=dn) + p["b2"]
    y = jax.nn.relu(y)
    y = y.reshape(y.shape[0], -1)
    y = jax.nn.relu(y @ p["fc1"] + p["bf1"])
    return y @ p["fc2"] + p["bf2"]


def _taps(n, k, stride):
    """Kernel taps that land inside the input, summed over the outputs
    of a SAME-padded convolution along one axis of length n."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(sum(0 <= i * stride - lo + j < n for j in range(k))
               for i in range(out)), out


def forward_flops(cfg):
    """2 x the multiply-adds of one example's forward pass that touch
    real inputs (taps on the zero padding are not work), from shapes."""
    m = cfg["model"]
    (h, w, cin), classes = m["input_shape"], m["num_classes"]
    c1, c2 = m["hidden"]
    k, fc = m["kernel_size"], m["fc_width"]
    th1, h1 = _taps(h, k, 2)
    tw1, w1 = _taps(w, k, 2)
    th2, h2 = _taps(h1, k, 2)
    tw2, w2 = _taps(w1, k, 2)
    macs = (th1 * tw1 * cin * c1 + th2 * tw2 * c1 * c2
            + h2 * w2 * c2 * fc + fc * classes)
    return 2 * macs
