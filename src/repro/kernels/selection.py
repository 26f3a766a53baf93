"""Pallas TPU kernel: fused peer selection (WPFed Eq. 6-8 in one pass).

The unfused round does hamming_matrix -> normalized_distance ->
selection_weights -> top_k, materializing three (M, M) arrays in HBM
plus a (M, M, W) XOR-broadcast intermediate. This kernel fuses the whole
chain: each program owns a (BM, M) row block of the weight matrix and
produces the per-row top-N ids/weights directly — nothing (M, M)-shaped
ever leaves VMEM (DESIGN.md §4).

Distance trick: instead of XOR + SWAR popcount (pure VPU integer work),
codes are unpacked to +-1 floats and the Gram matrix goes through the
MXU: dot(u_i, u_j) = agreements - disagreements = bits_tot - 2 * d_ij,
so d_ij = (bits_tot - dot) / 2. Every intermediate is an integer with
|value| <= bits_tot << 2^24, exact in f32 regardless of reduction
order — the kernel is therefore bit-exact against the jnp oracle
(ref.fused_select_ref, which computes the same integers via popcount +
an exp lookup table, the CPU-fast form) AND against the unfused
popcount composition. Caveat: the distances are exact everywhere, but
exp is not — in interpret mode kernel and oracle share XLA's exp
(bit-exact, tested); on compiled TPU, Mosaic's exp lowering could
differ from XLA's in the last ulp, which would flip selection order
only for weights within 1 ulp of each other. If TPU hardware ever
shows such divergence, pass the oracle's (bits+1)-entry LUT into the
kernel and gather instead of calling exp (DESIGN.md §4).

Weighting (Eq. 8): w_ij = s_j * exp(-gamma * d_ij / bits), with the
Table-3 ablation switches compiled in (use_lsh / use_rank static flags;
the both-off random ablation needs an rng and stays outside the kernel —
see core.neighbor.select_partners). Self-weights and padded columns are
masked to -inf before selection.

Top-N: N iterations of (max, first max, knock out) over the row block
(`_knockout_topn`, in the gather-free form Mosaic lowers). Taking the
first maximum reproduces jax.lax.top_k's tie-breaking (ascending index
among equal values), so selected ids match the unfused path exactly as
long as N <= M-1 (always true: the protocol clamps N to M-1, and every
non-self weight is finite).

The packed word axis is NOT padded: the arrays the kernel computes on
are the unpacked (rows, W*32) bit matrices, whose last dim is already
a lane multiple for any bits in {128, 256, 512, ...}. VMEM per program
~= (BM + M) * bits * 4 (unpacked codes) + BM * M * 4 (weights); at
BM=8, M=4096, bits=256 that is ~4.3 MB — `fused_select` therefore caps
at M ~ 10^4 clients.

`fused_select_tiled` removes that ceiling (DESIGN.md §10): a second
grid axis streams (BM, BK) *column tiles* of the same ±1 Gram matrix
while a VMEM scratch carries a per-row running top-N. Pass 1 is the
streamed merge-by-knockout: each tile's weights are read as one
concatenation with the running (vals, ids) candidates and N knockout
iterations keep the best N. Because earlier tiles hold strictly smaller
global column indices, putting the running candidates FIRST in that
order preserves `lax.top_k`'s first-max (ascending-index) tie-breaking
exactly; weights are the same exact-integer distances fed to the same
elementwise exp, so ids AND weights are bit-exact against
`ref.fused_select_ref` and the one-shot kernel at every M. Pass 2
(the Eq. 8 weighting itself) is unchanged — it is computed per tile
from the exact distances. VMEM per program ~= (BM + BK) * bits * 4 +
BM * BK * 4, independent of M.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.registry import kernel_contract
from repro.kernels import resolve_interpret

BM_SEL = 8          # row block (f32 sublane width)
BM_SEL_TILED = 128  # row block of the column-tiled kernel
BK_SEL = 512        # column tile of the column-tiled kernel
BM_ANN = 8          # row block of the ANN candidate kernel
BK_ANN = 256        # candidate tile of the ANN kernel (VMEM ~2 MB)


def unpack_pm1(words):
    """(R, W) packed uint32 -> (R, W*32) f32 in {-1, +1} (bit=1 -> +1).

    Stays 2-D with the bit axis on lanes: column k reads word k // 32
    (a W-way select of lane-broadcast word columns) and shifts by
    k % 32; the ±1 comes from a select, since Mosaic has no uint32 ->
    f32 cast and no lane-merging reshape. Lowers identically on TPU and
    in interpret mode."""
    r, w = words.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (r, w * 32), 1)
    word = jnp.zeros((r, w * 32), jnp.uint32)
    for i in range(w):                                # static, W = bits/32
        word = jnp.where(col // 32 == i, words[:, i:i + 1], word)
    bit = (word >> (col % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(bit != 0, 1.0, -1.0).astype(jnp.float32)


def _eq8_weights(d, s, row_ids, col_ids, *, bits: int, gamma: float,
                 m_real: int, use_lsh: bool, use_rank: bool):
    """Eq. 8 weighting + self/padding mask on a tile of exact integer
    distances. Shared VERBATIM by the dense kernels (via
    `_gram_weights`) and the ANN candidate kernel, so weights are
    bit-identical wherever the same (d, s) pair appears. `col_ids >=
    m_real` also masks the ANN path's sentinel candidate ids."""
    shape = d.shape
    if use_rank:
        w = jnp.broadcast_to(s, shape)
    else:
        w = jnp.ones(shape, jnp.float32)
    if use_lsh:
        w = w * jnp.exp(-gamma * (d / float(bits)))
    return jnp.where((col_ids == row_ids) | (col_ids >= m_real),
                     -jnp.inf, w)


def _gram_weights(a_words, b_words, s_row, row0, col0, *, bits: int,
                  gamma: float, m_real: int, use_lsh: bool, use_rank: bool):
    """Shared Eq. 6-8 tile: unpack -> ±1 Gram distances -> weights ->
    self/padding mask. Identical ops in the one-shot and tiled kernels,
    so the weights are bit-identical between them."""
    ua = unpack_pm1(a_words)                          # (BM, bits_tot)
    ub = unpack_pm1(b_words)                          # (BK, bits_tot)
    bits_tot = ua.shape[1]
    gram = jnp.dot(ua, ub.T, preferred_element_type=jnp.float32)
    d = (float(bits_tot) - gram) * 0.5                # exact integer f32

    bm, bk = d.shape
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
    w = _eq8_weights(d, s_row, row, col, bits=bits, gamma=gamma,
                     m_real=m_real, use_lsh=use_lsh, use_rank=use_rank)
    return w, col


def _knockout_topn(parts, nsel: int):
    """N iterations of (max, first max, knock out) over `parts`, a
    sequence of (vals, ids) candidate blocks read as ONE concatenation
    along the lane axis in the given order — reproduces lax.top_k's
    ascending-position tie-breaking over that concatenation.

    Written for Mosaic, which lowers no in-kernel gather, argmax or
    unaligned lane concatenation: the first max is a min over masked
    lane positions, the id pick an iota-compare select and sum, and the
    (BM, N) outputs fill lane by lane. Every step is exact."""
    vals = [v for v, _ in parts]
    pos = [jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) for v in vals]
    bm = vals[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, nsel), 1)
    top_v = jnp.zeros((bm, nsel), jnp.float32)
    top_i = jnp.zeros((bm, nsel), jnp.int32)
    for t in range(nsel):                             # static unroll
        best = functools.reduce(jnp.maximum, [
            jnp.max(v, axis=1, keepdims=True) for v in vals])
        taken = jnp.zeros((bm, 1), jnp.bool_)
        pick = jnp.zeros((bm, 1), jnp.int32)
        for k, (_, ids) in enumerate(parts):
            width = vals[k].shape[1]
            first = jnp.min(jnp.where(vals[k] == best, pos[k], width),
                            axis=1, keepdims=True)
            hit = (first < width) & ~taken            # earliest part wins
            at = (pos[k] == first) & hit
            pick = pick + jnp.sum(jnp.where(at, ids, 0), axis=1,
                                  keepdims=True)
            vals[k] = jnp.where(at, -jnp.inf, vals[k])
            taken = taken | hit
        top_v = jnp.where(lane == t, best, top_v)
        top_i = jnp.where(lane == t, pick, top_i)
    return top_v, top_i


def _select_kernel(a_ref, b_ref, s_ref, ids_ref, w_ref, *, bits: int,
                   gamma: float, nsel: int, m_real: int,
                   use_lsh: bool, use_rank: bool):
    row0 = pl.program_id(0) * BM_SEL
    w, col = _gram_weights(a_ref[...], b_ref[...], s_ref[...], row0, 0,
                           bits=bits, gamma=gamma, m_real=m_real,
                           use_lsh=use_lsh, use_rank=use_rank)
    vals, ids = _knockout_topn([(w, col)], nsel)
    ids_ref[...] = ids
    w_ref[...] = vals


# --- repro.analysis contract helpers (DESIGN.md §12) -----------------------
def _select_point_args(pt):
    """Abstract (ShapeDtypeStruct) args for a {m, bits} shape point."""
    w = pt["bits"] // 32
    args = (jax.ShapeDtypeStruct((pt["m"], w), jnp.uint32),
            jax.ShapeDtypeStruct((pt["m"],), jnp.float32))
    return args, dict(bits=pt["bits"], gamma=1.0, num_neighbors=16)


def _select_vmem_extra(site, pt):
    """Kernel-internal intermediates beyond the blocks, from the
    CAPTURED shapes: unpacked ±1 row/column codes + the (BM, M)
    weight tile (see the VMEM paragraph in the module docstring)."""
    bm, w = site.in_specs[0].block_shape
    mp = site.in_specs[1].block_shape[0]
    bits_tot = w * 32
    return (bm + mp) * bits_tot * 4 + bm * mp * 4


@kernel_contract(
    name="selection_oneshot", sites=1, oracle="fused_select_ref",
    estimator="selection_vmem_bytes", exactness="bit_exact",
    out_revisit=(),
    points=({"m": 256, "bits": 256}, {"m": 1024, "bits": 256},
            {"m": 768, "bits": 512}),
    make_args=_select_point_args,
    estimator_kwargs=lambda pt: {"m": pt["m"], "bits_tot": pt["bits"]},
    vmem_extra=_select_vmem_extra, slack=0.08)
@functools.partial(jax.jit, static_argnames=(
    "bits", "gamma", "num_neighbors", "use_lsh", "use_rank", "interpret"))
def fused_select(codes, scores, *, bits: int, gamma: float,
                 num_neighbors: int, use_lsh: bool = True,
                 use_rank: bool = True, interpret: bool | None = None):
    """Fused Eq. 6-8 + top-N. codes: (M, W) uint32, scores: (M,) f32
    -> (ids (M, N) int32, top_w (M, N) f32). Pads M to the row-block
    grid; padded rows are discarded and padded columns never win
    (masked to -inf in-kernel)."""
    m, w = codes.shape
    nsel = min(num_neighbors, m - 1)
    if nsel <= 0:                       # degenerate M <= 1 federation
        return (jnp.zeros((m, 0), jnp.int32), jnp.zeros((m, 0), jnp.float32))
    pm = (-m) % BM_SEL
    padded = jnp.pad(codes, ((0, pm), (0, 0)))
    scores_p = jnp.pad(scores.astype(jnp.float32), (0, pm))[None, :]
    mp = m + pm
    ids, top_w = pl.pallas_call(
        functools.partial(_select_kernel, bits=bits, gamma=gamma,
                          nsel=nsel, m_real=m, use_lsh=use_lsh,
                          use_rank=use_rank),
        grid=(mp // BM_SEL,),
        in_specs=[
            pl.BlockSpec((BM_SEL, w), lambda i: (i, 0)),
            pl.BlockSpec((mp, w), lambda i: (0, 0)),        # revisited
            pl.BlockSpec((1, mp), lambda i: (0, 0)),        # revisited
        ],
        out_specs=[
            pl.BlockSpec((BM_SEL, nsel), lambda i: (i, 0)),
            pl.BlockSpec((BM_SEL, nsel), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, nsel), jnp.int32),
            jax.ShapeDtypeStruct((mp, nsel), jnp.float32),
        ],
        name="fused_select",
        interpret=resolve_interpret(interpret),
    )(padded, padded, scores_p)
    return ids[:m], top_w[:m]


def _select_tiled_kernel(a_ref, b_ref, s_ref, ids_ref, w_ref,
                         vals_scr, ids_scr, *, bits: int, gamma: float,
                         nsel: int, m_real: int, use_lsh: bool,
                         use_rank: bool, bm: int, bk: int, nj: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, -jnp.inf)
        ids_scr[...] = jnp.zeros_like(ids_scr)

    row0 = pl.program_id(0) * bm
    w, col = _gram_weights(a_ref[...], b_ref[...], s_ref[...],
                           row0, j * bk, bits=bits, gamma=gamma,
                           m_real=m_real, use_lsh=use_lsh,
                           use_rank=use_rank)
    # Merge-by-knockout: running candidates FIRST — they come from
    # earlier column tiles, so their global ids are strictly smaller
    # and the first max keeps lax.top_k's ascending-index tie-breaking
    # across tile boundaries.
    vals, ids = _knockout_topn([(vals_scr[...], ids_scr[...]), (w, col)],
                               nsel)
    vals_scr[...] = vals
    ids_scr[...] = ids

    @pl.when(j == nj - 1)
    def _finalize():
        ids_ref[...] = ids_scr[...]
        w_ref[...] = vals_scr[...]


def _select_tiled_vmem_extra(site, pt):
    """Unpacked ±1 row/column-tile codes + the (BM, BK) weight tile,
    from the captured block shapes — O(tile), independent of M."""
    bm, w = site.in_specs[0].block_shape
    bk = site.in_specs[1].block_shape[0]
    bits_tot = w * 32
    return (bm + bk) * bits_tot * 4 + bm * bk * 4


@kernel_contract(
    name="selection_tiled", sites=1, oracle="fused_select_ref",
    estimator="selection_tiled_vmem_bytes", exactness="bit_exact",
    out_revisit=(1,),           # column-tile axis j accumulates top-N
    points=({"m": 1024, "bits": 256}, {"m": 2048, "bits": 256},
            {"m": 4096, "bits": 512}),
    make_args=_select_point_args,
    estimator_kwargs=lambda pt: {"bits_tot": pt["bits"]},
    vmem_extra=_select_tiled_vmem_extra, slack=0.10)
@functools.partial(jax.jit, static_argnames=(
    "bits", "gamma", "num_neighbors", "use_lsh", "use_rank", "interpret",
    "block_m", "block_k"))
def fused_select_tiled(codes, scores, *, bits: int, gamma: float,
                       num_neighbors: int, use_lsh: bool = True,
                       use_rank: bool = True, interpret: bool | None = None,
                       block_m: int = BM_SEL_TILED, block_k: int = BK_SEL):
    """Column-tiled two-pass fused selection (DESIGN.md §10): same
    contract as `fused_select` — (ids (M, N) int32, top_w (M, N) f32),
    bit-exact against it and `ref.fused_select_ref` — but VMEM per
    program is O(block_m * block_k) instead of O(block_m * M), so M is
    bounded by HBM, not VMEM. Rows pad to the `block_m` grid, columns
    to the `block_k` stream; padded columns are masked to -inf
    in-kernel and never win."""
    m, w = codes.shape
    nsel = min(num_neighbors, m - 1)
    if nsel <= 0:                       # degenerate M <= 1 federation
        return (jnp.zeros((m, 0), jnp.int32), jnp.zeros((m, 0), jnp.float32))
    import jax.experimental.pallas.tpu as pltpu
    bm = min(block_m, m + (-m) % BM_SEL)          # small-M: one row block
    pm = (-m) % bm
    rows = jnp.pad(codes, ((0, pm), (0, 0)))
    bk = min(block_k, m + (-m) % 128)             # small-M: one column tile
    pk = (-m) % bk
    cols = jnp.pad(codes, ((0, pk), (0, 0)))
    scores_p = jnp.pad(scores.astype(jnp.float32), (0, pk))[None, :]
    mr, mc = m + pm, m + pk
    nj = mc // bk
    ids, top_w = pl.pallas_call(
        functools.partial(_select_tiled_kernel, bits=bits, gamma=gamma,
                          nsel=nsel, m_real=m, use_lsh=use_lsh,
                          use_rank=use_rank, bm=bm, bk=bk, nj=nj),
        grid=(mr // bm, nj),                      # column tiles innermost
        in_specs=[
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, w), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, nsel), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, nsel), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mr, nsel), jnp.int32),
            jax.ShapeDtypeStruct((mr, nsel), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, nsel), jnp.float32),
            pltpu.VMEM((bm, nsel), jnp.int32),
        ],
        name="fused_select_tiled",
        interpret=resolve_interpret(interpret),
    )(rows, cols, scores_p)
    return ids[:m], top_w[:m]


def _select_ann_kernel(a_ref, c_ref, ci_ref, cs_ref, ids_ref, w_ref,
                       vals_scr, ids_scr, *, bits: int, gamma: float,
                       nsel: int, m_real: int, use_lsh: bool,
                       use_rank: bool, bm: int, bk: int, nj: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, -jnp.inf)
        ids_scr[...] = jnp.zeros_like(ids_scr)

    row0 = pl.program_id(0) * bm
    ua = unpack_pm1(a_ref[...])                       # (BM, bits_tot)
    cw = c_ref[...]                                   # (BM, BK, W)
    w_words = cw.shape[-1]
    uc = unpack_pm1(cw.reshape(bm * bk, w_words)).reshape(bm, bk, -1)
    bits_tot = ua.shape[1]
    # per-row Gram: each row has its OWN candidate codes, so this is a
    # multiply and lane sum (Mosaic lowers no matrix-vector batched
    # dot). Distances stay exact integers in f32 (§4).
    gram = jnp.sum(ua[:, None, :] * uc, axis=-1)      # (BM, BK)
    d = (float(bits_tot) - gram) * 0.5
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
    col = ci_ref[...]                                 # gathered global ids
    w = _eq8_weights(d, cs_ref[...], row, col, bits=bits, gamma=gamma,
                     m_real=m_real, use_lsh=use_lsh, use_rank=use_rank)
    # §10 knockout merge, running candidates FIRST: earlier candidate
    # tiles hold earlier candidate positions, so the first max
    # reproduces lax.top_k's tie-breaking over the full candidate axis
    # (and, in the one-bucket fallback where candidates are ascending
    # client ids, over the full client axis — the bit-exact case).
    vals, ids = _knockout_topn([(vals_scr[...], ids_scr[...]), (w, col)],
                               nsel)
    vals_scr[...] = vals
    ids_scr[...] = ids

    @pl.when(j == nj - 1)
    def _finalize():
        ids_ref[...] = ids_scr[...]
        w_ref[...] = vals_scr[...]


def _select_ann_point_args(pt):
    w = pt["bits"] // 32
    args = (jax.ShapeDtypeStruct((pt["m"], w), jnp.uint32),
            jax.ShapeDtypeStruct((pt["m"],), jnp.float32),
            jax.ShapeDtypeStruct((pt["m"], pt["k"]), jnp.int32))
    return args, dict(bits=pt["bits"], gamma=1.0, num_neighbors=16)


def _select_ann_vmem_extra(site, pt):
    """Unpacked ±1 row codes + per-row unpacked candidate codes + the
    (BM, BK) weight tile, from the captured block shapes."""
    bm, w = site.in_specs[0].block_shape
    bk = site.in_specs[1].block_shape[1]
    bits_tot = w * 32
    return (bm + bm * bk) * bits_tot * 4 + bm * bk * 4


@kernel_contract(
    name="selection_ann", sites=1, oracle="ann_select_ref",
    estimator="ann_vmem_bytes", exactness="bit_exact",
    out_revisit=(1,),           # candidate-tile axis j accumulates top-N
    points=({"m": 512, "k": 256, "bits": 256},
            {"m": 1024, "k": 512, "bits": 256},
            {"m": 512, "k": 256, "bits": 512}),
    make_args=_select_ann_point_args,
    estimator_kwargs=lambda pt: {"bits_tot": pt["bits"]},
    vmem_extra=_select_ann_vmem_extra, slack=0.08)
@functools.partial(jax.jit, static_argnames=(
    "bits", "gamma", "num_neighbors", "use_lsh", "use_rank", "interpret",
    "block_m", "block_k"))
def fused_select_ann(codes, scores, cand_ids, *, bits: int, gamma: float,
                     num_neighbors: int, use_lsh: bool = True,
                     use_rank: bool = True, interpret: bool | None = None,
                     block_m: int = BM_ANN, block_k: int = BK_ANN):
    """ANN candidate selection (DESIGN.md §11): exact Eq. 6-8 weights
    computed ONLY on `cand_ids` (the (M, K) per-client candidate sets
    from core.ann — bucket tiles + score teaser, sentinel id M in
    invalid slots), streamed in (block_m, block_k) tiles with the §10
    running top-N knockout merge. O(M*K*bits) FLOPs instead of
    O(M^2*bits); VMEM per program is O(tile).

    Bit-exact against `ref.ann_select_ref` on the same candidate sets
    (same exact integer distances, same exp inputs, same tie-breaking
    by candidate position), and — because the one-bucket fallback
    makes the candidate set every client in ascending id order —
    bit-exact against `fused_select` / `fused_select_ref` when
    `core.ann` is run with prefix_bits=0 (pinned in tests).

    Returns (ids (M, N) int32, top_w (M, N) f32); slots with no finite
    candidate get id 0 and weight -inf (callers mask on isfinite, as
    with the exact path's degenerate shapes).
    """
    m, w = codes.shape
    k = cand_ids.shape[1]
    nsel = min(num_neighbors, m - 1)
    if nsel <= 0:                       # degenerate M <= 1 federation
        return (jnp.zeros((m, 0), jnp.int32), jnp.zeros((m, 0), jnp.float32))
    import jax.experimental.pallas.tpu as pltpu
    bm = block_m
    pm = (-m) % bm
    bk = min(block_k, k + (-k) % 128)             # small-K: one tile
    pk = (-k) % bk
    # gather candidate codes/scores OUTSIDE the kernel (XLA gather);
    # the sentinel id M hits the appended zero row / zero score and is
    # masked to -inf in-kernel via col >= m_real, like padded columns.
    cand_p = jnp.pad(cand_ids.astype(jnp.int32), ((0, pm), (0, pk)),
                     constant_values=m)
    codes_pad = jnp.concatenate(
        [codes, jnp.zeros((1, w), codes.dtype)], axis=0)
    scores_pad = jnp.concatenate(
        [scores.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
    cand_codes = codes_pad[cand_p]                # (MR, KP, W)
    cand_scores = scores_pad[cand_p]              # (MR, KP)
    rows = jnp.pad(codes, ((0, pm), (0, 0)))
    mr, kp = m + pm, k + pk
    nj = kp // bk
    ids, top_w = pl.pallas_call(
        functools.partial(_select_ann_kernel, bits=bits, gamma=gamma,
                          nsel=nsel, m_real=m, use_lsh=use_lsh,
                          use_rank=use_rank, bm=bm, bk=bk, nj=nj),
        grid=(mr // bm, nj),                      # candidate tiles innermost
        in_specs=[
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bk, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, nsel), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, nsel), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mr, nsel), jnp.int32),
            jax.ShapeDtypeStruct((mr, nsel), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, nsel), jnp.float32),
            pltpu.VMEM((bm, nsel), jnp.int32),
        ],
        name="fused_select_ann",
        interpret=resolve_interpret(interpret),
    )(rows, cand_codes, cand_p, cand_scores)
    ids, top_w = ids[:m], top_w[:m]
    # no-finite-candidate slots: pin the id to 0 (matches the twin's
    # clamp) so downstream gathers stay in range; sel_mask is False.
    return jnp.where(jnp.isfinite(top_w), ids, 0), top_w
