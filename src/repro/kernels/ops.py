"""Jitted public wrappers around the Pallas kernels.

These handle padding/packing (each kernel compiles on TPU and runs in
interpret mode elsewhere — `repro.kernels.resolve_interpret`) and
expose pytree-level convenience APIs used by repro.core.lsh. The
pure-jnp semantics live in ref.py; tests assert the kernel and oracle
agree bit-exactly across shape/dtype sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hamming import BM, BN, hamming_all_pairs
from repro.kernels.lsh_projection import (BLOCK_M, CHUNK,
                                          lsh_project_sums,
                                          lsh_project_sums_batched)


def resolve_backend(backend: str) -> str:
    """Delegates to the single validated resolver in
    repro.core.backends (function-level import: repro.core's package
    __init__ pulls in the whole protocol, which imports this module)."""
    from repro.core.backends import resolve
    return resolve(backend)


def flatten_params(params) -> jnp.ndarray:
    """Pytree -> single f32 vector, padded to a CHUNK multiple."""
    leaves = [jnp.ravel(x).astype(jnp.float32)
              for x in jax.tree.leaves(params)]
    flat = jnp.concatenate(leaves) if leaves else jnp.zeros((0,), jnp.float32)
    pad = (-flat.shape[0]) % CHUNK
    return jnp.pad(flat, (0, pad))


def pack_bits(sums) -> jnp.ndarray:
    """Sign bits of projection sums -> packed uint32 words (little-endian
    within each word). sums: (..., bits) with bits % 32 == 0."""
    bits = (sums > 0).astype(jnp.uint32)
    *lead, b = bits.shape
    words = bits.reshape(*lead, b // 32, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(words * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(codes, bits: int) -> jnp.ndarray:
    words = codes[..., :, None]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    out = ((words >> shifts) & jnp.uint32(1)).astype(jnp.uint32)
    return out.reshape(*codes.shape[:-1], codes.shape[-1] * 32)[..., :bits]


def flatten_params_batched(stacked_params) -> jnp.ndarray:
    """Stacked (M, ...) pytree -> (M, P) f32 matrix, P padded to a CHUNK
    multiple. Row i equals flatten_params of client i's subtree (same
    leaf order, same ravel)."""
    leaves = [x.reshape(x.shape[0], -1).astype(jnp.float32)
              for x in jax.tree.leaves(stacked_params)]
    flat = jnp.concatenate(leaves, axis=1)
    pad = (-flat.shape[1]) % CHUNK
    return jnp.pad(flat, ((0, 0), (0, pad)))


def batched_lsh_codes(flat2d, seed, *, bits: int = 256,
                      use_kernel: bool = True):
    """WPFed Eq. (5) over the stacked client axis: (M, P) f32 (P a CHUNK
    multiple) -> (M, W) packed uint32 codes. Kernel path pads M to the
    BLOCK_M row grid; padded rows are discarded."""
    m = flat2d.shape[0]
    if use_kernel:
        pm = (-m) % BLOCK_M
        x = jnp.pad(flat2d, ((0, pm), (0, 0)))
        sums = lsh_project_sums_batched(x, seed, bits=bits)[:m]
    else:
        sums = ref.lsh_project_sums_batched_ref(flat2d, seed, bits=bits)
    return pack_bits(sums)


def lsh_code(params, seed, *, bits: int = 256, use_kernel: bool = True):
    """WPFed Eq. (5): packed uint32 LSH code of a parameter pytree."""
    flat = flatten_params(params)
    if use_kernel:
        sums = lsh_project_sums(flat, seed, bits=bits)
    else:
        sums = ref.lsh_project_sums_ref(flat, seed, bits=bits)
    return pack_bits(sums)


def hamming_matrix(codes, *, use_kernel: bool = True):
    """WPFed Eq. (6) for all pairs: codes (M, W) uint32 -> (M, M) int32.

    Pads M to the kernel tile grid and the word axis to the 128-lane
    width; padding words are zero so they contribute 0 to distances.
    """
    m, w = codes.shape
    if not use_kernel:
        return ref.hamming_all_pairs_ref(codes, codes)
    pm = (-m) % max(BM, BN)
    pw = (-w) % 128
    padded = jnp.pad(codes, ((0, pm), (0, pw)))
    d = hamming_all_pairs(padded, padded)
    return d[:m, :m]


def gqa_flash_attention(q, k, v, *, causal: bool = True,
                        use_kernel: bool = True):
    """GQA wrapper: q (B, Sq, H, dh), k/v (B, Sk, KV, dh) -> (B, Sq, H, dh).
    Expands KV heads to H (gather view) and maps onto the (N, S, dh)
    kernel layout."""
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    qk = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, dh)
    kx = jnp.repeat(jnp.moveaxis(k, 2, 1), g, axis=1).reshape(b * h, -1, dh)
    vx = jnp.repeat(jnp.moveaxis(v, 2, 1), g, axis=1).reshape(b * h, -1, dh)
    if use_kernel:
        o = flash_attention(qk, kx, vx, causal=causal)
    else:
        o = ref.flash_attention_ref(qk, kx, vx, causal=causal)
    return jnp.moveaxis(o.reshape(b, h, sq, dh), 1, 2)
