"""Pallas TPU kernels (one module each), their jnp twins (`ref.py`) and
the jitted wrappers the protocol calls (`ops.py`).

This package init imports only jax: `repro.core.backends` imports it,
and every kernel module reads its interpret-mode rule from here.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one rule for Pallas interpret mode. An explicit value wins
    (tests run the interpreter on the CPU with True, and compile for a
    described chip with False); otherwise kernels compile on a TPU and
    run in the interpreter on every other platform. No kernel entry
    point defaults to the interpreter on the chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
