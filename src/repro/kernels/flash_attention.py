"""Pallas TPU kernel: flash-attention forward (online softmax).

The §Perf analysis (EXPERIMENTS.md iteration 6) showed dense train_4k is
memory-bound on the f32 S^2 score chain; the JAX-level chunked attention
fixes the accounting, but the TPU-native answer is this kernel: scores
and probabilities never leave VMEM — HBM traffic reduces to Q/K/V/O.

Layout: q (N, Sq, dh), k/v (N, Sk, dh) with N = batch*heads (the ops.py
wrapper maps GQA onto this). Grid (N, Sq/BQ, Sk/BK), KV innermost so
each program accumulates into the same (BQ, dh) VMEM scratch with the
standard online-softmax correction; the last KV step writes the
normalized output block.

VMEM per program ~= (BQ + 2*BK) * dh * 4 + BQ * BK * 4 + BQ * dh * 4
bytes; defaults (BQ=BK=256, dh<=256) < 2 MB. MXU dims (BQ, dh, BK) are
128-multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.registry import kernel_contract
from repro.kernels import resolve_interpret

BQ = 256
BK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                      # (BQ, dh)
    k = k_ref[0].astype(jnp.float32)                      # (BK, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        qi = pl.program_id(1)
        qpos = qi * q_ref.shape[1] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        kpos = ki * k_ref.shape[1] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _flash_point_args(pt):
    n, sq, sk, dh = pt["n"], pt["sq"], pt["sk"], pt["dh"]
    q = jax.ShapeDtypeStruct((n, sq, dh), jnp.float32)
    kv = jax.ShapeDtypeStruct((n, sk, dh), jnp.float32)
    return (q, kv, kv), dict(causal=True)


@kernel_contract(
    name="flash_attention", sites=1, oracle="flash_attention_ref",
    estimator=None, exactness="tolerance",
    out_revisit=(2,),           # KV axis accumulates into scratch
    points=({"n": 2, "sq": 512, "sk": 512, "dh": 128},
            {"n": 1, "sq": 1024, "sk": 512, "dh": 64}),
    make_args=_flash_point_args)
@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float = 0.0,
                    interpret: bool | None = None):
    """q: (N, Sq, dh), k/v: (N, Sk, dh) -> (N, Sq, dh)."""
    n, sq, dh = q.shape
    sk = k.shape[1]
    bq, bk = min(BQ, sq), min(BK, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk)
    nq, nk = sq // bq, sk // bk
    scale = scale or dh ** -0.5
    import jax.experimental.pallas.tpu as pltpu
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal, nk=nk),
        grid=(n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
