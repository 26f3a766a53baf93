"""P2P knowledge distillation (WPFed §3.1, Eq. 2-4, Alg. 1 l.19).

The combined per-client objective:

    L_i = alpha * CE(f(theta_i, X_loc), Y_loc)
        + (1 - alpha) * || f(theta_i, X_ref) - mean_j Yhat_j ||^2

where Yhat_j = f(theta_j, X_i^ref) are the (stop-gradient) neighbor
outputs that passed LSH verification.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy(logits, labels):
    """Mean CE; the label pick is the exchange kernel's iota compare,
    select and sum (exact: one nonzero term), so the fused exchange's
    Eq. 3 losses equal this function's in the same compiled program."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    cls = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    nll = -jnp.sum(jnp.where(cls == labels[..., None], logp, 0.0), axis=-1)
    return jnp.mean(nll)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))


def aggregate_neighbor_outputs(neighbor_logits, valid_mask):
    """mean over valid neighbors. neighbor_logits: (N, R, C); mask (N,).

    Falls back to zeros-weight (no distillation signal) when no neighbor
    passes verification — the local loss term then dominates.
    """
    w = valid_mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    agg = jnp.einsum("n,nrc->rc", w, neighbor_logits) / denom
    has_any = jnp.sum(w) > 0
    return agg, has_any


def combined_loss(apply_fn, params, batch, ref_x, target_ref_logits,
                  has_target, alpha: float):
    """Alg. 1 line 19. batch: {"x","y"} local minibatch."""
    local_logits = apply_fn(params, batch["x"])
    l_loc = cross_entropy(local_logits, batch["y"])
    own_ref = apply_fn(params, ref_x)
    l_ref = jnp.mean(jnp.square(own_ref
                                - jax.lax.stop_gradient(target_ref_logits)))
    l_ref = jnp.where(has_target, l_ref, 0.0)
    return alpha * l_loc + (1 - alpha) * l_ref, (l_loc, l_ref)
