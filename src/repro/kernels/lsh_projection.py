"""Pallas TPU kernel: sign-random-projection LSH over a parameter vector.

WPFed Eq. (5): lsh_i = LSH(theta_i, b). At LLM scale the parameter vector
has up to 10^12 entries, so the P x b Gaussian projection matrix of the
textbook construction can never be materialized. We instead use a
*Rademacher* (+-1) projection whose entries are generated on the fly
inside the kernel from a counter-based integer hash of (param_index,
bit_index, seed) — an equally valid angular-distance LSH (sign random
projection only needs a symmetric sub-Gaussian row distribution), with
zero memory traffic for the projection matrix. This is the TPU-native
adaptation recorded in DESIGN.md §3.

Grid (single client): one program per parameter chunk; each program
materializes a (CHUNK, BITS) +-1 block in VREGs via iota hashing,
computes the (1, CHUNK) x (CHUNK, BITS) partial product on the MXU, and
accumulates into the (1, BITS) output block (revisited across the whole
grid).

Batched variant (DESIGN.md §4): the federation hot path hashes ALL M
clients per round, so `lsh_project_sums_batched` runs a 2D grid over
(client-block, chunk) directly on the stacked (M, P) parameter matrix.
Each program computes a (BLOCK_M, CHUNK) x (CHUNK, BITS) partial
product — the Rademacher block is generated ONCE per chunk step and
shared by all BLOCK_M clients in the block, amortizing the hash
arithmetic M-fold versus vmapping the single-client kernel (which has
no batching rule anyway). Chunk is the innermost grid axis so the
(BLOCK_M, BITS) output block accumulates across chunk steps in the
same chunk order as the single-client kernel; within-chunk matmul
reduction order may differ by shape, so projection *sums* agree to f32
tolerance while the packed sign-bit *codes* are bit-exact (tested).

VMEM budget per program ~= CHUNK*4 (x block) + CHUNK*BITS*4 (R block)
+ BITS*4 bytes; defaults (2048, 256) ~= 2.1 MB. The batched kernel
multiplies the x and out terms by BLOCK_M (default 8): ~2.2 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.registry import kernel_contract
from repro.kernels import resolve_interpret

CHUNK = 2048
BLOCK_M = 8        # client rows per batched program (f32 sublane width)
_K1 = 2654435761   # Knuth multiplicative hash (plain ints: pallas kernels
_K2 = 40503        # may not close over externally-created jax arrays)
_K3 = 2246822519


def rademacher_block(i0, chunk, bits, seed):
    """Deterministic +-1 block R[i0:i0+chunk, :bits] (f32).

    Shared by kernel and oracle (ref.py imports it) — the hash is pure
    uint32 arithmetic so it lowers identically on TPU and in interpret
    mode on CPU.
    """
    i = (jnp.uint32(i0) + jax.lax.broadcasted_iota(jnp.uint32, (chunk, bits), 0))
    j = jax.lax.broadcasted_iota(jnp.uint32, (chunk, bits), 1)
    h = i * jnp.uint32(_K1) ^ (j * jnp.uint32(_K2)
                               + jnp.uint32(seed) * jnp.uint32(_K3))
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(_K3)
    h = h ^ (h >> jnp.uint32(13))
    bit = (h >> jnp.uint32(9)) & jnp.uint32(1)
    # a select, not a cast: Mosaic has no uint32 -> f32 conversion
    return jnp.where(bit != 0, -1.0, 1.0).astype(jnp.float32)


def _lsh_kernel(seed_ref, x_ref, out_ref, *, bits: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)                    # (1, CHUNK)
    r = rademacher_block(step * CHUNK, CHUNK, bits, seed_ref[0])
    out_ref[...] += jnp.dot(x, r, preferred_element_type=jnp.float32)


@kernel_contract(
    name="lsh_single", sites=1, oracle="lsh_project_sums_ref",
    estimator=None, exactness="tolerance",
    out_revisit=(0,),           # the (1, bits) block accumulates chunks
    points=({"p": 4096, "bits": 256}, {"p": 8192, "bits": 256}),
    make_args=lambda pt: (
        (jax.ShapeDtypeStruct((pt["p"],), jnp.float32),),
        dict(seed=7, bits=pt["bits"])))
@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def lsh_project_sums(x, seed, *, bits: int = 256,
                     interpret: bool | None = None):
    """x: (P,) f32 (P padded to CHUNK by the caller) -> (bits,) f32 sums."""
    assert x.ndim == 1 and x.shape[0] % CHUNK == 0, x.shape
    n_chunks = x.shape[0] // CHUNK
    x2 = x.reshape(n_chunks, CHUNK)
    seed_arr = jnp.asarray([seed], jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_lsh_kernel, bits=bits),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),            # seed (revisited)
            pl.BlockSpec((1, CHUNK), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bits), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, bits), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(seed_arr, x2)
    return out[0]


def _lsh_batched_kernel(seed_ref, x_ref, out_ref, *, bits: int):
    chunk_step = pl.program_id(1)

    @pl.when(chunk_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)                    # (BLOCK_M, CHUNK)
    r = rademacher_block(chunk_step * CHUNK, CHUNK, bits, seed_ref[0])
    out_ref[...] += jnp.dot(x, r, preferred_element_type=jnp.float32)


@kernel_contract(
    name="lsh_batched", sites=1, oracle="lsh_project_sums_batched_ref",
    estimator=None, exactness="tolerance",
    out_revisit=(1,),           # chunk axis accumulates into (BM, bits)
    points=({"m": 16, "p": 4096, "bits": 256},
            {"m": 8, "p": 8192, "bits": 256}),
    make_args=lambda pt: (
        (jax.ShapeDtypeStruct((pt["m"], pt["p"]), jnp.float32),),
        dict(seed=7, bits=pt["bits"])))
@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def lsh_project_sums_batched(x, seed, *, bits: int = 256,
                             interpret: bool | None = None):
    """Batched Eq. (5) over the stacked client axis.

    x: (M, P) f32 with M % BLOCK_M == 0 and P % CHUNK == 0 (caller pads;
    see ops.batched_lsh_codes) -> (M, bits) f32 projection sums.

    Grid is (M // BLOCK_M, P // CHUNK) with chunk innermost, so each
    (BLOCK_M, bits) output block is revisited across its row of chunk
    programs and accumulates in the same chunk order as the
    single-client kernel.
    """
    assert x.ndim == 2 and x.shape[0] % BLOCK_M == 0 \
        and x.shape[1] % CHUNK == 0, x.shape
    m, p = x.shape
    seed_arr = jnp.asarray(jnp.reshape(seed, (1,)), jnp.uint32)
    return pl.pallas_call(
        functools.partial(_lsh_batched_kernel, bits=bits),
        grid=(m // BLOCK_M, p // CHUNK),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),         # seed (revisited)
            pl.BlockSpec((BLOCK_M, CHUNK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_M, bits), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bits), jnp.float32),
        name="lsh_project_sums_batched",
        interpret=resolve_interpret(interpret),
    )(seed_arr, x)
