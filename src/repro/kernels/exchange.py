"""Pallas TPU kernel: fused all-in-one exchange (WPFed Eq. 3 + §3.5 +
the distillation-target mean in a single pass).

The unfused round ran three separate log-softmax passes over the same
(M, N, R, C) neighbor-logit tensor — one inside `distill.cross_entropy`
(Eq. 3), one inside `verify.kl_divergence` (§3.5), and then re-read the
tensor a third time for `distill.aggregate_neighbor_outputs`. This
kernel computes ONE shared neighbor log-softmax per client block and
derives all three results from it while the (N, R, C) tile sits in
VMEM (DESIGN.md §7):

  * Eq. 3 CE losses l_ij, the label logit picked by an iota compare,
    select and sum (Mosaic lowers no in-kernel gather; the sum has one
    nonzero term, so the pick is exact);
  * §3.5 output-KL divergences against the client's own reference
    outputs, plus the upper-half keep filter. The rank is computed in
    counting form — rank(n) = #{m : kl_m < kl_n} + #{m < n : kl_m ==
    kl_n} — which equals the stable-argsort rank the unfused
    `verify.lsh_verification_mask` derives from a double argsort
    (jnp.argsort is stable; ties break ascending-index), at O(N^2)
    compares instead of an in-kernel sort Mosaic would struggle with;
  * the masked distillation-target mean over the neighbors that passed
    (zeros fallback when none do — `has_target` is derived from the
    returned mask by the wrapper, it is a free reduction).

Bit-exactness (tests/test_exchange_pipeline.py): every derived value
consumes the same floats in the same reduction order as the jnp oracle
twin (`ref.all_in_one_exchange_ref`, jitted like the kernel), so kernel
and oracle agree bit-exactly in interpret mode; the oracle in turn is
bit-identical to the unfused cross_entropy -> lsh_verification_mask ->
aggregate_neighbor_outputs composition the round used to run, compiled
as one program. On the chip, Mosaic's exp/log and reduction order may
differ from XLA's in the last ulps: compiled kernel and oracle agree
to a tolerance, not bitwise.

Mosaic layout: the client block is 8 rows (the f32 sublane tile), and
every per-neighbor value (labels' CE, KL, the §3.5 mask, the selection
mask) lives as (BM, N, 1, 1), so the neighbor axis never has to move
onto lanes.

VMEM per program ~= BM_EXC * (N + 2) * R * C * 4 bytes for the logit
tiles (at BM=8, N=16, R=64, C=1024 that is ~38 MB) — `fused_exchange`
therefore caps near C ~ 10^2-10^3; vocab-scale reference sets need
`fused_exchange_streamed` (DESIGN.md §10): a (client-block, R-tile,
C-tile) grid that streams (BM, N, BR, BC) blocks with a
flash-attention-style online max / log-sum-exp for the shared neighbor
log-softmax (see kernels/flash_attention.py). CE reduces to
lse_nb - x_nb[y] (the label logit is picked as C tiles stream by),
the §3.5 output-KL to B/A - lse_own + lse_nb where A/B are online
exp-weighted sums, and the per-row means accumulate across R tiles.
Exactness contract (DESIGN.md §10): the online reductions REORDER the
softmax sums, so the streamed path is NOT bit-exact against the
one-shot oracle — l_ij and target are tolerance-bounded (last-ulp
scale) against both `ref.all_in_one_exchange_ref` and the streaming
jnp twin `ref.streamed_exchange_ref` (same tile walk; XLA's
fusion-dependent FMA/reassociation rewrites keep even kernel-vs-twin
agreement at the ulp level rather than bitwise), while the §3.5 valid
mask only flips on exact kl ties and is pinned EQUAL in tests. The
one-shot kernel/oracle pair remains the bit-exact default; backend
resolution (`core.backends.resolve_tiling`) only picks the streamed
path when the one-shot working set exceeds the VMEM budget. The
distillation-target mean is a second, stateless pass
(`_target_kernel`) over the same tiles once the §3.5 mask is known;
its per-element N-contraction is unchanged by R/C tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.registry import kernel_contract
from repro.kernels import resolve_interpret

BM_EXC = 8          # client block per program (f32 sublane width)
BR_EXC = 8          # reference-row tile of the streamed kernel
BC_EXC = 512        # class-column tile of the streamed kernel


def _upper_half_mask(kl_mean, sel_int):
    """§3.5 upper-half keep filter in counting-rank form, shared by the
    one-shot and streamed kernels: rank(n) = #{m : kl_m < kl_n} +
    #{m < n : kl_m == kl_n} (the stable-argsort rank). The neighbor
    axis is axis 1 of any (BM, N, ...) layout; the static loop over m
    compares one neighbor slice against all, so no (N, N) relayout is
    needed in Mosaic."""
    selm = sel_int != 0
    kls = jnp.where(selm, kl_mean, jnp.inf)
    n = kls.shape[1]
    idx = jax.lax.broadcasted_iota(jnp.int32, kls.shape, 1)
    keep = (jnp.sum(sel_int, axis=1, keepdims=True) + 1) // 2
    rank_of = jnp.zeros(kls.shape, jnp.int32)
    for m in range(n):                                # static, N neighbors
        km = kls[:, m:m + 1]
        rank_of = rank_of + ((km < kls) | ((km == kls) & (m < idx))
                             ).astype(jnp.int32)      # stable-sort rank
    return (rank_of < keep) & selm


def _exchange_kernel(own_ref, nb_ref, y_ref, sel_ref,
                     l_ref, valid_ref, target_ref, *,
                     lsh_verification: bool):
    # Layout: every per-neighbor value keeps (R, C)'s two minor axes as
    # unit dims — (BM, N, 1, 1) — so the neighbor axis never moves onto
    # lanes (Mosaic cannot reshape (BM, N) <-> (BM, N, 1, 1)).
    nb = nb_ref[...].astype(jnp.float32)              # (BM, N, R, C)
    logp_nb = jax.nn.log_softmax(nb, axis=-1)         # ONE shared pass

    # Eq. 3: CE of each neighbor's logits on the reference labels; the
    # label pick is an iota compare, select and sum (exact: one term)
    cls = jax.lax.broadcasted_iota(jnp.int32, nb.shape, 3)
    nll = -jnp.sum(jnp.where(cls == y_ref[...][:, None], logp_nb, 0.0),
                   axis=-1, keepdims=True)            # (BM, N, R, 1)
    l_ref[...] = jnp.mean(nll, axis=2, keepdims=True)

    # §3.5: output-KL upper-half filter over the selected slots
    if lsh_verification:
        logp_own = jax.nn.log_softmax(
            own_ref[...].astype(jnp.float32), axis=-1)  # (BM, R, C)
        kl = jnp.sum(jnp.exp(logp_own)[:, None]
                     * (logp_own[:, None] - logp_nb), axis=-1,
                     keepdims=True)
        valid = _upper_half_mask(jnp.mean(kl, axis=2, keepdims=True),
                                 sel_ref[...])
    else:
        valid = sel_ref[...] != 0
    valid_ref[...] = valid.astype(jnp.int32)

    # masked distillation-target mean (zeros fallback when none pass)
    target_ref[...] = _masked_mean(valid.astype(jnp.float32), nb)


def _over_rows(x, r: int):
    """(..., 1, 1) -> (..., R, 1). Mosaic widens a value along sublanes
    or along lanes, never both in one broadcast; the iota select keeps
    the compiler from folding this sublane step into the lane
    broadcast that follows. Exact: it selects x everywhere."""
    shape = x.shape[:-2] + (r, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, x.ndim - 2)
    return jnp.where(rows >= 0, x, 0.0)


def _masked_mean(w, nb):
    """Distillation-target mean over the neighbor axis: w (BM, N, 1, 1)
    0/1 weights, nb (BM, N, R, C) -> (BM, R, C)."""
    r = nb.shape[2]
    denom = jnp.maximum(jnp.sum(w, axis=1), 1.0)      # (BM, 1, 1)
    return jnp.sum(_over_rows(w, r) * nb, axis=1) / _over_rows(denom, r)


# --- repro.analysis contract helpers (DESIGN.md §12) -----------------------
def _exchange_point_args(pt):
    """Abstract (ShapeDtypeStruct) args for an {m, n, r, c} point."""
    m, n, r, c = pt["m"], pt["n"], pt["r"], pt["c"]
    args = (jax.ShapeDtypeStruct((m, r, c), jnp.float32),
            jax.ShapeDtypeStruct((m, n, r, c), jnp.float32),
            jax.ShapeDtypeStruct((m, r), jnp.int32),
            jax.ShapeDtypeStruct((m, n), jnp.bool_))
    return args, dict(lsh_verification=True)


@kernel_contract(
    name="exchange_oneshot", sites=1, oracle="all_in_one_exchange_ref",
    estimator="exchange_vmem_bytes", exactness="bit_exact",
    out_revisit=(),
    points=({"m": 8, "n": 8, "r": 32, "c": 512},
            {"m": 8, "n": 16, "r": 64, "c": 1024},
            {"m": 4, "n": 4, "r": 16, "c": 256}),
    make_args=_exchange_point_args,
    estimator_kwargs=lambda pt: {"n": pt["n"], "r": pt["r"],
                                 "c": pt["c"]},
    slack=0.05)
@functools.partial(jax.jit, static_argnames=("lsh_verification",
                                             "interpret"))
def fused_exchange(own_logits, neighbor_logits, y_ref, sel_mask, *,
                   lsh_verification: bool = True,
                   interpret: bool | None = None):
    """Fused Eq. 3 + §3.5 + target mean. own_logits: (M, R, C);
    neighbor_logits: (M, N, R, C); y_ref: (M, R) int; sel_mask: (M, N)
    bool -> (l_ij (M, N) f32, valid (M, N) bool, target_ref (M, R, C)
    f32, has_target (M,) bool). Pads M to the client-block grid; padded
    rows carry an all-False selection mask and are discarded."""
    m, n, r, c = neighbor_logits.shape
    pm = (-m) % BM_EXC
    own_p = jnp.pad(own_logits.astype(jnp.float32),
                    ((0, pm), (0, 0), (0, 0)))
    nb_p = jnp.pad(neighbor_logits.astype(jnp.float32),
                   ((0, pm), (0, 0), (0, 0), (0, 0)))
    y_p = jnp.pad(y_ref.astype(jnp.int32), ((0, pm), (0, 0)))[..., None]
    sel_p = jnp.pad(sel_mask.astype(jnp.int32),
                    ((0, pm), (0, 0)))[..., None, None]
    mp = m + pm
    l_ij, valid, target = pl.pallas_call(
        functools.partial(_exchange_kernel,
                          lsh_verification=lsh_verification),
        grid=(mp // BM_EXC,),
        in_specs=[
            pl.BlockSpec((BM_EXC, r, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((BM_EXC, n, r, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((BM_EXC, r, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((BM_EXC, n, 1, 1), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BM_EXC, n, 1, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((BM_EXC, n, 1, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((BM_EXC, r, c), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, n, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((mp, r, c), jnp.float32),
        ],
        name="fused_exchange",
        interpret=resolve_interpret(interpret),
    )(own_p, nb_p, y_p, sel_p)
    l_ij = l_ij[:m, :, 0, 0]
    valid = valid[:m, :, 0, 0].astype(bool)
    return l_ij, valid, target[:m], jnp.any(valid, axis=-1)


# ---------------------------------------------------------------------------
# streamed (R/C-tiled) variant — vocab-scale reference sets
# ---------------------------------------------------------------------------
def _streamed_stats_kernel(own_ref, nb_ref, y_ref, sel_ref,
                           l_ref, valid_ref,
                           l_acc, kl_acc, m_nb, a_nb, g_nb, b_x,
                           m_own, a_own, *, lsh_verification: bool,
                           r_real: int, c_real: int, br: int, bc: int,
                           nr: int, nc: int):
    ri = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when((ri == 0) & (ci == 0))
    def _init_round():
        l_acc[...] = jnp.zeros_like(l_acc)
        kl_acc[...] = jnp.zeros_like(kl_acc)

    @pl.when(ci == 0)
    def _init_tile():
        m_nb[...] = jnp.full_like(m_nb, -jnp.inf)
        a_nb[...] = jnp.zeros_like(a_nb)
        g_nb[...] = jnp.zeros_like(g_nb)
        b_x[...] = jnp.zeros_like(b_x)
        m_own[...] = jnp.full_like(m_own, -jnp.inf)
        a_own[...] = jnp.zeros_like(a_own)

    xo = own_ref[...].astype(jnp.float32)             # (BM, BR, BC)
    xn = nb_ref[...].astype(jnp.float32)              # (BM, N, BR, BC)
    col = ci * bc + jax.lax.broadcasted_iota(jnp.int32, (bc,), 0)
    cvalid = col < c_real                             # (BC,)
    xo_m = jnp.where(cvalid, xo, -jnp.inf)
    xn_m = jnp.where(cvalid, xn, -jnp.inf)

    # online max / sum-exp (flash-attention correction; every C tile
    # contains at least one real column, so the new max is finite and
    # the correction factors never see inf - inf)
    mo_new = jnp.maximum(m_own[...], jnp.max(xo_m, axis=-1))
    co = jnp.exp(m_own[...] - mo_new)
    po = jnp.exp(xo_m - mo_new[..., None])            # (BM, BR, BC)
    a_own[...] = a_own[...] * co + jnp.sum(po, axis=-1)
    mn_new = jnp.maximum(m_nb[...], jnp.max(xn_m, axis=-1))
    cn = jnp.exp(m_nb[...] - mn_new)
    a_nb[...] = (a_nb[...] * cn
                 + jnp.sum(jnp.exp(xn_m - mn_new[..., None]), axis=-1))
    # cross term of the §3.5 KL: sum_c exp(x_own - m) * (x_own - x_nb)
    b_x[...] = (b_x[...] * co[:, None]
                + jnp.sum(po[:, None] * (xo[:, None] - xn), axis=-1))
    # Eq. 3 label-logit gather: the C tile holding y contributes x[y]
    # exactly once (raw logits, exact zeros elsewhere)
    match = col[None, None, :] == y_ref[...]          # (BM, BR, BC)
    g_nb[...] = g_nb[...] + jnp.sum(
        jnp.where(match[:, None], xn, 0.0), axis=-1)
    m_own[...] = mo_new
    m_nb[...] = mn_new

    @pl.when(ci == nc - 1)
    def _fold_tile():
        lse_nb = m_nb[...] + jnp.log(a_nb[...])       # (BM, N, BR)
        lse_own = m_own[...] + jnp.log(a_own[...])    # (BM, BR)
        rvalid = (ri * br
                  + jax.lax.broadcasted_iota(jnp.int32, (br,), 0)) < r_real
        nll = lse_nb - g_nb[...]
        l_acc[...] = l_acc[...] + jnp.sum(
            jnp.where(rvalid, nll, 0.0), axis=-1)
        kl_r = (b_x[...] / a_own[...][:, None]
                - lse_own[:, None] + lse_nb)
        kl_acc[...] = kl_acc[...] + jnp.sum(
            jnp.where(rvalid, kl_r, 0.0), axis=-1)

    @pl.when((ri == nr - 1) & (ci == nc - 1))
    def _finalize():
        l_ref[...] = l_acc[...] / float(r_real)
        if lsh_verification:
            valid = _upper_half_mask(kl_acc[...] / float(r_real),
                                     sel_ref[...])
        else:
            valid = sel_ref[...] != 0
        valid_ref[...] = valid.astype(jnp.int32)


def _target_kernel(nb_ref, w_ref, t_ref):
    """Masked distillation-target mean over one (BM, N, BR, BC) tile.
    Stateless: the N-contraction is per output element, so R/C tiling
    does not change its value."""
    t_ref[...] = _masked_mean(w_ref[...].astype(jnp.float32),
                              nb_ref[...].astype(jnp.float32))


def streamed_tiles(r: int, c: int, block_r: int, block_c: int):
    """Clamp the (BR, BC) tile to the (8, 128)-padded problem so small
    shapes run as a single tile; returns (br, pr, bc, pc)."""
    br = min(block_r, r + (-r) % 8)
    bc = min(block_c, c + (-c) % 128)
    return br, (-r) % br, bc, (-c) % bc


@kernel_contract(
    name="exchange_streamed", sites=2, oracle="streamed_exchange_ref",
    estimator="exchange_tiled_vmem_bytes", exactness="tolerance",
    # stats site: outputs land once at (i, 0) while the (ri, ci) tile
    # axes accumulate into scratch; target site writes (i, ri, ci)
    # exactly once.
    out_revisit=((1, 2), ()),
    points=({"m": 8, "n": 8, "r": 32, "c": 2048},
            {"m": 4, "n": 16, "r": 64, "c": 1024},
            {"m": 4, "n": 8, "r": 16, "c": 4096}),
    make_args=_exchange_point_args,
    estimator_kwargs=lambda pt: {"n": pt["n"]},
    slack=0.05)
@functools.partial(jax.jit, static_argnames=(
    "lsh_verification", "interpret", "block_m", "block_r", "block_c"))
def fused_exchange_streamed(own_logits, neighbor_logits, y_ref, sel_mask,
                            *, lsh_verification: bool = True,
                            interpret: bool | None = None,
                            block_m: int = BM_EXC, block_r: int = BR_EXC,
                            block_c: int = BC_EXC):
    """Streamed Eq. 3 + §3.5 + target mean (DESIGN.md §10): same
    contract as `fused_exchange`, but VMEM per program is
    O(BM * N * BR * BC) — R and C are bounded by HBM, not VMEM.
    Tolerance-bounded against the one-shot pair and the streaming twin
    `ref.streamed_exchange_ref` (the online softmax reorders the
    reductions; the §3.5 mask flips only on exact kl ties — see the
    module docstring for the full §10 contract)."""
    m, n, r, c = neighbor_logits.shape
    import jax.experimental.pallas.tpu as pltpu
    bm = min(block_m, m + (-m) % BM_EXC)
    pm = (-m) % bm
    br, pr, bc, pc = streamed_tiles(r, c, block_r, block_c)
    own_p = jnp.pad(own_logits.astype(jnp.float32),
                    ((0, pm), (0, pr), (0, pc)))
    nb_p = jnp.pad(neighbor_logits.astype(jnp.float32),
                   ((0, pm), (0, 0), (0, pr), (0, pc)))
    y_p = jnp.pad(y_ref.astype(jnp.int32), ((0, pm), (0, pr)))
    sel_p = jnp.pad(sel_mask.astype(jnp.int32), ((0, pm), (0, 0)))
    mp, nr, nc = m + pm, (r + pr) // br, (c + pc) // bc
    l_ij, valid = pl.pallas_call(
        functools.partial(_streamed_stats_kernel,
                          lsh_verification=lsh_verification,
                          r_real=r, c_real=c, br=br, bc=bc, nr=nr, nc=nc),
        grid=(mp // bm, nr, nc),                      # C innermost
        in_specs=[
            pl.BlockSpec((bm, br, bc), lambda i, ri, ci: (i, ri, ci)),
            pl.BlockSpec((bm, n, br, bc),
                         lambda i, ri, ci: (i, 0, ri, ci)),
            pl.BlockSpec((bm, br, 1), lambda i, ri, ci: (i, ri, 0)),
            pl.BlockSpec((bm, n), lambda i, ri, ci: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i, ri, ci: (i, 0)),
            pl.BlockSpec((bm, n), lambda i, ri, ci: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n), jnp.float32),
            jax.ShapeDtypeStruct((mp, n), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, n), jnp.float32),         # l_acc
            pltpu.VMEM((bm, n), jnp.float32),         # kl_acc
            pltpu.VMEM((bm, n, br), jnp.float32),     # running max (nb)
            pltpu.VMEM((bm, n, br), jnp.float32),     # running sum-exp (nb)
            pltpu.VMEM((bm, n, br), jnp.float32),     # label-logit gather
            pltpu.VMEM((bm, n, br), jnp.float32),     # KL cross term
            pltpu.VMEM((bm, br), jnp.float32),        # running max (own)
            pltpu.VMEM((bm, br), jnp.float32),        # running sum-exp (own)
        ],
        interpret=resolve_interpret(interpret),
    )(own_p, nb_p, y_p[..., None], sel_p)
    target = pl.pallas_call(
        _target_kernel,
        grid=(mp // bm, nr, nc),
        in_specs=[
            pl.BlockSpec((bm, n, br, bc),
                         lambda i, ri, ci: (i, 0, ri, ci)),
            pl.BlockSpec((bm, n, 1, 1), lambda i, ri, ci: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, br, bc), lambda i, ri, ci: (i, ri, ci)),
        out_shape=jax.ShapeDtypeStruct((mp, r + pr, c + pc), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(nb_p, valid[..., None, None])
    valid_b = valid[:m].astype(bool)
    return (l_ij[:m], valid_b, target[:m, :r, :c],
            jnp.any(valid_b, axis=-1))
