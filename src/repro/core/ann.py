"""LSH codes as a real ANN index (sub-quadratic selection, DESIGN.md §11).

The exact selection path prices every candidate pair: O(M^2 * bits)
FLOPs per round even after VMEM tiling (§10). But the published LSH
codes ALREADY encode proximity (Eq. 5-6) — close models agree on most
bits — so they can drive a bucketed candidate index the way
"Find Your Friends" restricts collaborator search to a sparse graph:

  1. *Prefix bucketing.* A per-round seeded permutation of the code's
     bit positions picks `prefix_bits` bits; clients sharing that
     prefix land in the same bucket (B = 2^prefix_bits buckets).
  2. *Multi-probe.* Each client also probes the buckets reached by
     flipping one prefix bit at a time (up to `probes` flips) — the
     standard multi-probe LSH recall knob: near-neighbors that
     straddle a bucket boundary differ in few prefix bits.
  3. *Score teaser.* Eq. 8 weights are s_j * exp(-gamma d/bits), so a
     globally high-ranked client can out-weigh a nearby one; distance
     buckets alone cannot see that. Every candidate set therefore
     also includes the global top-`teaser` ranking scores (one
     lax.top_k over M — O(M log M), not O(M^2)).

Exact Hamming -> Eq. 8 weights are then computed ONLY on the
candidate set (kernels.selection.fused_select_ann or the jnp twin
ref.ann_select_ref), and the per-bucket partial top-N merge reuses
the §10 knockout merge.

Everything here is pure jnp with STATIC shapes: buckets are laid out
as a padded (B, cap) table (stable sort by bucket id -> rank within
bucket -> scatter; overflow beyond `cap` is dropped from the
*candidate* side only — every client still queries with its own code).
Invalid slots (padding, empty probe buckets, teaser duplicates) carry
the sentinel id M, which the selection kernels mask to -inf exactly
like padded columns. The permutation seed is threaded from
`state.round` — the SAME per-round seed discipline as the LSH
projection itself (protocol.announce_phase), so reselection is
reproducible and scan-safe with a traced round index, and every peer
can recompute the bucketing from public information (the trust story
is unchanged: candidates come from codes everyone can verify).

Degenerate-bucket fallback: with `prefix_bits=0` there is ONE bucket
whose capacity is forced to M, so the candidate set is every client
in ascending id order and the ANN path is bit-exact against
`fused_select` / `fused_select_ref` (pinned in tests). The same holds
for all-identical codes at any prefix length: the shared bucket keeps
the first `cap` ids and the teaser covers the score order, which is
all the exact top-N can contain.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Knuth multiplicative hash constants — the same counter-hash family
# as kernels.lsh_projection.rademacher_block, so the bucket
# permutation is "seeded like the projection" in mechanism, not just
# in spirit.
_K1 = 2654435761
_K2 = 40503
_K3 = 2246822519

MAX_PREFIX_BITS = 16        # 2^16 buckets bounds the table scatter


class AnnCandidates(NamedTuple):
    """Static-shape candidate layout for one round of ANN selection."""
    ids: jnp.ndarray       # (M, K) int32 candidate ids; invalid = M
    bucket: jnp.ndarray    # (M,) int32 bucket id per client
    counts: jnp.ndarray    # (B,) int32 bucket occupancy (pre-cap)
    dropped: jnp.ndarray   # () int32 clients beyond cap (candidate side)


def effective_prefix_bits(prefix_bits: int, bits_tot: int) -> int:
    """Static clamp: cannot take more prefix bits than the code has,
    and the bucket table is bounded at 2^MAX_PREFIX_BITS rows."""
    return max(0, min(prefix_bits, bits_tot, MAX_PREFIX_BITS))


def effective_probes(probes: int, prefix_bits: int) -> int:
    """Static clamp: single-bit probes can flip at most every prefix
    bit once (prefix_bits=0 leaves only the home bucket)."""
    return max(0, min(probes, prefix_bits))


def bucket_cap(m: int, prefix_bits: int, num_neighbors: int) -> int:
    """Static per-bucket candidate capacity: 4x the uniform occupancy
    but never fewer than N+1 ids (a full bucket must be able to serve
    a whole top-N by itself), never more than M. The 4x multiplier is
    measured, not guessed: clustered codes concentrate whole clusters
    into single buckets (occupancy ~ M/n_clusters, not M/B), and at 2x
    the overflow drops cost ~10 recall points on a clustered-code
    sweep (`occupancy_stats` reports `dropped_candidates` so the effect
    stays observable). prefix_bits=0 forces cap=M — the one-bucket
    exact fallback."""
    n_buckets = 1 << effective_prefix_bits(prefix_bits, 1 << 30)
    uniform = -(-m // n_buckets)                       # ceil(M / B)
    return min(m, max(num_neighbors + 1, 4 * uniform))


def teaser_count(m: int, num_neighbors: int) -> int:
    """Static size of the global top-score candidate tile."""
    return min(m, max(2 * num_neighbors, 16))


def candidate_count(m: int, prefix_bits: int, probes: int,
                    num_neighbors: int, bits_tot: int = 1 << 30) -> int:
    """Static K: candidates per client = (probes + 1) bucket tiles of
    `cap` plus the score teaser. The FLOP estimators in
    core.backends price the ANN path with this K."""
    pb = effective_prefix_bits(prefix_bits, bits_tot)
    np_ = effective_probes(probes, pb)
    return ((np_ + 1) * bucket_cap(m, pb, num_neighbors)
            + teaser_count(m, num_neighbors))


def prefix_bit_indices(bits_tot: int, prefix_bits: int, seed):
    """Seeded permutation of code bit positions; the first
    `prefix_bits` form the bucket prefix. `seed` may be a traced
    scalar (state.round) — the hash is pure uint32 arithmetic and the
    argsort is shape-static, so this is jit/scan-safe with NO host
    RNG anywhere on the ANN path."""
    i = jnp.arange(bits_tot, dtype=jnp.uint32)
    s = jnp.asarray(seed).astype(jnp.uint32)
    h = i * jnp.uint32(_K1) ^ (i * jnp.uint32(_K2) + s * jnp.uint32(_K3))
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(_K3)
    h = h ^ (h >> jnp.uint32(13))
    order = jnp.argsort(h)                   # ties break by bit index
    return order[:prefix_bits].astype(jnp.int32)


def bucket_ids(codes, bit_idx):
    """Extract the (traced) prefix bit positions from packed uint32
    codes -> (M,) int32 bucket ids in [0, 2^prefix_bits)."""
    m = codes.shape[0]
    pb = bit_idx.shape[0]
    if pb == 0:
        return jnp.zeros((m,), jnp.int32)
    words = jnp.take(codes, bit_idx // 32, axis=1)       # (M, pb)
    bits = (words >> (bit_idx % 32).astype(jnp.uint32)) & jnp.uint32(1)
    weights = (jnp.uint32(1) << jnp.arange(pb, dtype=jnp.uint32))[None, :]
    return jnp.sum(bits * weights, axis=1).astype(jnp.int32)


def probe_masks(prefix_bits: int, probes: int):
    """Static XOR mask sequence: home bucket first, then single-bit
    flips of prefix bit 0, 1, ... (the prefix bits are already a
    seeded permutation of code positions, so the flip order is seeded
    too). Probed buckets are pairwise distinct, so no candidate can
    appear in two bucket tiles."""
    np_ = effective_probes(probes, prefix_bits)
    return jnp.asarray([0] + [1 << t for t in range(np_)], jnp.int32)


def build_bucket_table(bucket, m: int, n_buckets: int, cap: int):
    """Padded (B, cap) table of client ids per bucket.

    Stable sort by bucket id keeps ids ASCENDING within a bucket —
    the invariant the knockout merge needs to reproduce lax.top_k's
    first-max tie-breaking in the one-bucket exact fallback. Returns
    (table (B, cap) int32 padded with sentinel M, counts (B,) int32
    true occupancy, rank (M,) int32 position of each client within
    its bucket — rank >= cap means the client was dropped as a
    CANDIDATE by overflow, though it still queries normally)."""
    order = jnp.argsort(bucket, stable=True).astype(jnp.int32)
    sb = bucket[order]
    first = jnp.searchsorted(sb, sb, side="left")
    rank_sorted = (jnp.arange(m, dtype=jnp.int32)
                   - first.astype(jnp.int32))
    slot = sb * cap + rank_sorted
    ok = rank_sorted < cap
    flat = jnp.full((n_buckets * cap + 1,), m, jnp.int32)
    flat = flat.at[jnp.where(ok, slot, n_buckets * cap)].set(order)
    table = flat[:-1].reshape(n_buckets, cap)
    counts = jnp.zeros((n_buckets,), jnp.int32).at[bucket].add(1)
    rank = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
    return table, counts, rank


def ann_candidates(codes, scores, *, seed, prefix_bits: int, probes: int,
                   num_neighbors: int) -> AnnCandidates:
    """One round of candidate generation: seeded prefix bucketing +
    multi-probe + score teaser -> (M, K) candidate ids with sentinel
    M in every invalid slot (bucket padding, teaser duplicates).

    Valid entries in a row are pairwise DISTINCT: probed buckets are
    distinct and partition clients, and teaser entries already present
    in a probed bucket tile (probed AND rank < cap) are masked to the
    sentinel. Self ids are left in (the selection kernels self-mask
    exactly like the exact path). All shapes are static; `seed` may be
    traced."""
    m, w = codes.shape
    bits_tot = w * 32
    pb = effective_prefix_bits(prefix_bits, bits_tot)
    n_buckets = 1 << pb
    cap = bucket_cap(m, pb, num_neighbors)
    masks = probe_masks(pb, probes)

    bit_idx = prefix_bit_indices(bits_tot, pb, seed)
    bucket = bucket_ids(codes, bit_idx)
    table, counts, rank = build_bucket_table(bucket, m, n_buckets, cap)

    probed = bucket[:, None] ^ masks[None, :]            # (M, P+1)
    cand = table[probed].reshape(m, -1)                  # (M, (P+1)*cap)

    t = teaser_count(m, num_neighbors)
    _, top_ids = jax.lax.top_k(scores.astype(jnp.float32), t)
    top_ids = top_ids.astype(jnp.int32)
    tb = bucket[top_ids]                                 # (T,)
    in_probe = jnp.any(tb[None, :, None] == probed[:, None, :], axis=-1)
    dup = in_probe & (rank[top_ids] < cap)[None, :]      # already a cand
    teaser = jnp.where(dup, jnp.int32(m),
                       jnp.broadcast_to(top_ids[None, :], (m, t)))
    ids = jnp.concatenate([cand, teaser], axis=1)
    dropped = jnp.sum(jnp.maximum(counts - cap, 0))
    return AnnCandidates(ids, bucket, counts, dropped)


def occupancy_stats(c: AnnCandidates) -> dict:  # analysis: host-ok
    """Host-side candidate-set accounting for benchmarks: speedups
    must be attributable to a measured candidate count, not asserted.
    (Whole-function `host-ok`: every extraction here is the point.)"""
    import numpy as np
    counts = np.asarray(c.counts)
    nonempty = counts[counts > 0]
    return {
        "k": int(c.ids.shape[1]),
        "buckets": int(counts.size),
        "nonempty_buckets": int(nonempty.size),
        "mean_occupancy": round(float(nonempty.mean()), 2) if
        nonempty.size else 0.0,
        "max_occupancy": int(counts.max()) if counts.size else 0,
        "dropped_candidates": int(c.dropped),
    }
