"""Pallas TPU kernel: all-pairs Hamming distance over packed LSH codes.

WPFed Eq. (6): d_ij = HammingDist(lsh_i, lsh_j). Codes are bit-packed
into uint32 words (W words = bits/32, zero-padded to the 128-lane TPU
register width by ops.py). Each grid program computes one (BM, BN) output
tile: XOR-broadcast (BM, 1, W) ^ (1, BN, W), SWAR popcount, reduce over
the word axis. Pure VPU integer work — no MXU.

VMEM per program ~= (BM + BN) * W * 4 + BM * BN * W * 4 bytes;
defaults (32, 128, W=128) ~= 2.2 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.registry import kernel_contract
from repro.kernels import resolve_interpret

BM = 32
BN = 128


def popcount_u32(v):
    """SWAR popcount for uint32 arrays (shared with ref.py)."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2))
                                        & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _hamming_kernel(a_ref, b_ref, out_ref):
    a = a_ref[...]                                        # (BM, W) uint32
    b = b_ref[...]                                        # (BN, W) uint32
    x = a[:, None, :] ^ b[None, :, :]                     # (BM, BN, W)
    out_ref[...] = jnp.sum(popcount_u32(x), axis=-1)


@kernel_contract(
    name="hamming", sites=1, oracle="hamming_all_pairs_ref",
    estimator=None, exactness="bit_exact",
    out_revisit=(),             # each (BM, BN) tile is written once
    points=({"m": 64, "n": 256, "w": 8}, {"m": 32, "n": 128, "w": 8},
            {"m": 96, "n": 384, "w": 16}),
    make_args=lambda pt: (
        (jax.ShapeDtypeStruct((pt["m"], pt["w"]), jnp.uint32),
         jax.ShapeDtypeStruct((pt["n"], pt["w"]), jnp.uint32)), {}))
@functools.partial(jax.jit, static_argnames=("interpret",))
def hamming_all_pairs(codes_a, codes_b, *, interpret: bool | None = None):
    """codes: (M, W) x (N, W) uint32 (M % BM == 0, N % BN == 0, caller
    pads) -> (M, N) int32 distances."""
    m, w = codes_a.shape
    n = codes_b.shape[0]
    assert m % BM == 0 and n % BN == 0, (m, n)
    return pl.pallas_call(
        _hamming_kernel,
        grid=(m // BM, n // BN),
        in_specs=[
            pl.BlockSpec((BM, w), lambda i, j: (i, 0)),
            pl.BlockSpec((BN, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes_a, codes_b)
