"""Personalized neighbor selection (WPFed §3.4, Eq. 6-8).

`select_partners` is the single protocol entry point: published LSH
codes + crowd-sourced ranking scores -> per-client top-N partner ids.
It owns the backend switch (DESIGN.md §4):

  "kernel" -> fused Pallas kernel (Hamming -> Eq. 8 weights -> top-N in
              one pass; interpret-mode off-TPU),
  "oracle" -> the bit-exact fused jnp twin (ref.fused_select_ref),
  "auto"   -> kernel on TPU, oracle elsewhere.

The unfused pieces (`selection_weights`, `select_neighbors`) remain the
semantic reference — tests assert the fused paths match their
composition bit-exactly. Ablation switches reproduce Table 3:
  use_lsh=False  -> w_ij = s_j            ("w/o LSH")
  use_rank=False -> w_ij = exp(-gamma d)  ("w/o Rank")
  both False     -> uniform random selection ("w/o LSH & Rank")
The both-off random ablation draws from an rng and always runs the jnp
path (no kernel involvement regardless of backend).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ann, backends
from repro.kernels import ops, ref
from repro.kernels.selection import (fused_select, fused_select_ann,
                                     fused_select_tiled)


def selection_weights(scores, dist_norm, gamma: float, *,
                      use_lsh: bool = True, use_rank: bool = True,
                      rng=None):
    """scores: (M,) f32; dist_norm: (M, M) f32 in [0,1] -> (M, M) f32."""
    m = dist_norm.shape[0]
    if use_rank:
        w = jnp.broadcast_to(scores[None, :], (m, m))
    else:
        w = jnp.ones((m, m), jnp.float32)
    if use_lsh:
        w = w * jnp.exp(-gamma * dist_norm)
    if not use_rank and not use_lsh:
        assert rng is not None, "random selection needs an rng key"
        w = jax.random.uniform(rng, (m, m))
    return jnp.where(jnp.eye(m, dtype=bool), -jnp.inf, w)


def select_neighbors(weights, num_neighbors: int):
    """Top-N per row. weights: (M, M) -> ids (M, N) int32, mask (M, N)."""
    n = min(num_neighbors, weights.shape[1] - 1)
    top_w, top_i = jax.lax.top_k(weights, n)
    mask = jnp.isfinite(top_w)
    return top_i.astype(jnp.int32), mask


def select_partners(codes, scores, fed, *, rng=None, backend=None,
                    tiling=None, seed=0, active=None):
    """Eq. 6-8 + top-N in one call: the WPFed partner-selection step.

    codes: (M, W) uint32 published LSH codes; scores: (M,) f32 ranking
    scores (Eq. 7, reporter-filtered by the caller); fed: FedConfig
    (consumes num_neighbors, gamma, lsh_bits, use_lsh, use_rank,
    selection_backend, selection_tiling, ann_prefix_bits, ann_probes).
    rng is required only for the random ablation (use_lsh=False,
    use_rank=False). `backend` / `tiling` override
    fed.selection_backend / fed.selection_tiling when given. `seed`
    (may be a traced scalar — protocol.select_phase passes
    state.round) seeds the ANN bucket permutation; the exact paths
    ignore it.

    The kernel path picks one-shot vs column-tiled from the explicit
    VMEM estimate (`backends.resolve_tiling`, DESIGN.md §10); both are
    bit-exact against the oracle, so the choice never moves results.
    The oracle is the jnp twin either way (CPU memory is not
    VMEM-bounded).

    The "ann" path (DESIGN.md §11) restricts the exact Eq. 6-8
    weighting to LSH-bucket candidate sets — O(M*K*bits) instead of
    O(M^2*bits). "auto" opts into it only past the FLOP thresholds in
    `backends.resolve_selection`, so approximation is never silent at
    small M.

    `active` (M,) bool excludes departed clients (the service layer's
    churn-as-masking, DESIGN.md §13) by forcing their score column to
    -inf BEFORE backend dispatch: -inf survives the Eq. 8 multiply in
    every backend (oracle / kernel / tiled / ann — IEEE -inf times a
    positive finite weight stays -inf) and `isfinite(top_w)` already
    masks it out of the result, so no backend needs a mask argument.
    Requires use_rank=True — with Eq. 8 ignoring scores there is no
    column to carry the exclusion (and the ablations model a fixed
    cohort anyway).

    Returns (ids (M, N) int32, sel_mask (M, N) bool). With N <= M-1
    every selected id is a real, non-self client and the mask is all
    True; the mask exists for degenerate M <= 1 federations (and, on
    the ann path, for rows whose candidate set ran dry — the score
    teaser makes that impossible for M >= 2).
    """
    m = codes.shape[0]
    n = min(fed.num_neighbors, m - 1)
    if active is not None:
        if not fed.use_rank:
            raise ValueError(
                "select_partners(active=...) requires use_rank=True: "
                "membership exclusion rides the Eq. 8 score column "
                "(DESIGN.md §13)")
        scores = jnp.where(active, scores, -jnp.inf)
    if not fed.use_lsh and not fed.use_rank:
        w = selection_weights(scores, jnp.zeros((m, m), jnp.float32),
                              fed.gamma, use_lsh=False, use_rank=False,
                              rng=rng)
        return select_neighbors(w, n)
    bits_tot = codes.shape[1] * 32
    k = ann.candidate_count(m, fed.ann_prefix_bits, fed.ann_probes, n,
                            bits_tot)
    resolved = backends.resolve_selection(
        backend or fed.selection_backend, m,
        exact_flops=backends.selection_flops(m, bits_tot),
        ann_flops=backends.ann_selection_flops(m, bits_tot, k))
    if resolved == "ann":
        # tiling strings stay validated even though the ann kernel has
        # exactly one (streaming) layout
        backends.resolve_tiling(tiling or fed.selection_tiling, 0)
        cand = ann.ann_candidates(
            codes, scores, seed=seed, prefix_bits=fed.ann_prefix_bits,
            probes=fed.ann_probes, num_neighbors=n)
        if backends.resolve("auto") == "kernel":
            ids, top_w = fused_select_ann(
                codes, scores, cand.ids, bits=fed.lsh_bits,
                gamma=fed.gamma, num_neighbors=n, use_lsh=fed.use_lsh,
                use_rank=fed.use_rank)
        else:
            ids, top_w = ref.ann_select_ref(
                codes, scores, cand.ids, bits=fed.lsh_bits,
                gamma=fed.gamma, num_neighbors=n, use_lsh=fed.use_lsh,
                use_rank=fed.use_rank)
        return ids, jnp.isfinite(top_w)
    if resolved == "kernel":
        bits_tot = codes.shape[1] * 32
        resolved_tiling = backends.resolve_tiling(
            tiling or fed.selection_tiling,
            backends.selection_vmem_bytes(m, bits_tot))
        select_fn = (fused_select_tiled if resolved_tiling == "tiled"
                     else fused_select)
        ids, top_w = select_fn(
            codes, scores, bits=fed.lsh_bits, gamma=fed.gamma,
            num_neighbors=n, use_lsh=fed.use_lsh, use_rank=fed.use_rank)
    else:
        backends.resolve_tiling(tiling or fed.selection_tiling, 0)
        ids, top_w = ref.fused_select_ref(
            codes, scores, bits=fed.lsh_bits, gamma=fed.gamma,
            num_neighbors=n, use_lsh=fed.use_lsh, use_rank=fed.use_rank)
    return ids, jnp.isfinite(top_w)
