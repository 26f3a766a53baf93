"""Pure-jnp oracles for the Pallas kernels (bit-exact references)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lsh_projection import CHUNK, rademacher_block
from repro.kernels.hamming import popcount_u32


def lsh_project_sums_ref(x, seed, *, bits: int = 256):
    """Oracle for lsh_projection: same on-the-fly Rademacher matrix,
    single dense matmul. x: (P,) with P % CHUNK == 0."""
    p = x.shape[0]
    r = rademacher_block(0, p, bits, seed)
    return jnp.dot(x.astype(jnp.float32), r)


def lsh_project_sums_batched_ref(x2d, seed, *, bits: int = 256):
    """Per-client oracle for the batched LSH kernel: vmap of the single
    full-width matmul. x2d: (M, P) with P % CHUNK == 0 -> (M, bits).

    Sums may differ from the chunk-accumulating kernel in the last f32
    ulps (different reduction order); the packed sign-bit codes are
    bit-exact (asserted in tests)."""
    return jax.vmap(
        lambda v: lsh_project_sums_ref(v, seed, bits=bits))(x2d)


def fused_select_ref(codes, scores, *, bits: int, gamma: float,
                     num_neighbors: int, use_lsh: bool = True,
                     use_rank: bool = True):
    """Oracle for the fused selection kernel: XOR+popcount distances
    (CPU-fast; the kernel's +-1 Gram matmul produces the same exact
    integers on the MXU), Eq. 8 weighting through a discrete-domain
    exp LUT, self-mask, lax.top_k.

    The LUT trick (DESIGN.md §4): d only takes integer values in
    [0, W*32], so exp(-gamma * d / bits) is a gather into a
    (W*32 + 1)-entry table whose entries are jnp.exp evaluated on
    exactly the inputs the direct formula would see — bit-identical
    weights at M^2 loads instead of M^2 transcendentals.

    codes: (M, W) uint32, scores: (M,) f32 ->
    (ids (M, N) int32, top_w (M, N) f32).
    """
    m = codes.shape[0]
    nsel = min(num_neighbors, m - 1)
    d = hamming_all_pairs_ref(codes, codes)            # exact int32
    if use_rank:
        w = jnp.broadcast_to(scores.astype(jnp.float32)[None, :], (m, m))
    else:
        w = jnp.ones((m, m), jnp.float32)
    if use_lsh:
        dmax = codes.shape[1] * 32
        table = jnp.exp(-gamma * (
            jnp.arange(dmax + 1, dtype=jnp.float32) / float(bits)))
        w = w * table[d]
    w = jnp.where(jnp.eye(m, dtype=bool), -jnp.inf, w)
    top_w, top_i = jax.lax.top_k(w, nsel)
    return top_i.astype(jnp.int32), top_w


def ann_select_ref(codes, scores, cand_ids, *, bits: int, gamma: float,
                   num_neighbors: int, use_lsh: bool = True,
                   use_rank: bool = True):
    """Twin of `kernels.selection.fused_select_ann` (DESIGN.md §11):
    exact XOR+popcount distances and Eq. 8 LUT weights computed only
    on the (M, K) candidate sets from `core.ann` (sentinel id M in
    invalid slots), then one lax.top_k over the candidate axis.

    Bit-exact against the kernel: distances are the same exact
    integers, the LUT entries are jnp.exp on the same inputs the
    kernel's elementwise exp sees (the `fused_select_ref` argument),
    and top_k's first-max tie-breaking by candidate position matches
    the kernel's running-candidates-first knockout merge. Slots with
    no finite candidate get id 0 / weight -inf, same as the kernel's
    clamp. This is also the CPU-fast ANN path `core.neighbor`
    dispatches to off-TPU.
    """
    m = codes.shape[0]
    nsel = min(num_neighbors, m - 1)
    if nsel <= 0:
        return (jnp.zeros((m, 0), jnp.int32), jnp.zeros((m, 0), jnp.float32))
    cand = cand_ids.astype(jnp.int32)
    codes_pad = jnp.concatenate(
        [codes, jnp.zeros((1, codes.shape[1]), codes.dtype)], axis=0)
    cand_codes = codes_pad[cand]                       # (M, K, W)
    d = jnp.sum(popcount_u32(codes[:, None, :] ^ cand_codes), axis=-1)
    if use_rank:
        scores_pad = jnp.concatenate(
            [scores.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
        w = scores_pad[cand]
    else:
        w = jnp.ones(cand.shape, jnp.float32)
    if use_lsh:
        dmax = codes.shape[1] * 32
        table = jnp.exp(-gamma * (
            jnp.arange(dmax + 1, dtype=jnp.float32) / float(bits)))
        w = w * table[d]
    row = jnp.arange(m, dtype=jnp.int32)[:, None]
    w = jnp.where((cand == row) | (cand >= m), -jnp.inf, w)
    top_w, pos = jax.lax.top_k(w, nsel)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    return (jnp.where(jnp.isfinite(top_w), ids, 0).astype(jnp.int32),
            top_w)


@functools.partial(jax.jit, static_argnames=("lsh_verification",))
def all_in_one_exchange_ref(own_logits, neighbor_logits, y_ref, sel_mask,
                            *, lsh_verification: bool = True):
    """Oracle for the fused exchange kernel (WPFed Eq. 3 + §3.5 + the
    distillation-target mean in one shared log-softmax pass).

    own_logits: (M, R, C) f32 — each client's outputs on its reference
    set; neighbor_logits: (M, N, R, C) f32 — selected neighbors' outputs
    on the same set; y_ref: (M, R) int32 labels; sel_mask: (M, N) bool.

    Returns (l_ij (M, N) f32, valid (M, N) bool, target_ref (M, R, C)
    f32, has_target (M,) bool). Semantics are bit-identical to the
    unfused composition the round used to run (`distill.cross_entropy`
    -> `verify.lsh_verification_mask` -> `distill
    .aggregate_neighbor_outputs`): the neighbor log-softmax that the CE
    and KL terms both consume is a deterministic elementwise-row op, so
    computing it once is exact, and the §3.5 rank is the stable-argsort
    rank in counting form (ties break ascending-index, matching
    jnp.argsort). Tested in tests/test_exchange_pipeline.py.

    Jitted, like the kernel: XLA-CPU's log/exp round differently in a
    fused program and op by op, so the twin is compared as one compiled
    program. The label pick (iota compare, select, sum) and the target
    sum are the kernel's own formulation.
    """
    own = own_logits.astype(jnp.float32)
    nb = neighbor_logits.astype(jnp.float32)
    logp_nb = jax.nn.log_softmax(nb, axis=-1)           # ONE shared pass
    # Eq. 3: per-neighbor CE on the reference labels
    cls = jnp.arange(nb.shape[-1], dtype=jnp.int32)
    nll = -jnp.sum(jnp.where(cls == y_ref[:, None, :, None].astype(jnp.int32),
                             logp_nb, 0.0), axis=-1)
    l_ij = jnp.mean(nll, axis=-1)                       # (M, N)
    # §3.5: output-KL similarity, upper-half filter over selected slots
    if lsh_verification:
        logp_own = jax.nn.log_softmax(own, axis=-1)     # (M, R, C)
        kl = jnp.sum(jnp.exp(logp_own)[:, None]
                     * (logp_own[:, None] - logp_nb), axis=-1)
        kls = jnp.where(sel_mask, jnp.mean(kl, axis=-1), jnp.inf)
        n_valid = jnp.sum(sel_mask.astype(jnp.int32), axis=-1, keepdims=True)
        keep = (n_valid + 1) // 2
        lt = kls[:, :, None] < kls[:, None, :]          # rank candidates n
        eq = kls[:, :, None] == kls[:, None, :]
        n_idx = jnp.arange(kls.shape[1])
        first = n_idx[:, None] < n_idx[None, :]         # m before n
        rank_of = jnp.sum(lt | (eq & first), axis=1)    # stable-sort rank
        valid = (rank_of < keep) & sel_mask
    else:
        valid = sel_mask
    # masked distillation-target mean (zeros fallback when none pass)
    w = valid.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w, axis=-1), 1.0)
    target = (jnp.sum(w[:, :, None, None] * nb, axis=1)
              / denom[:, None, None])
    has_target = jnp.sum(w, axis=-1) > 0
    return l_ij, valid, target, has_target


def streamed_exchange_ref(own_logits, neighbor_logits, y_ref, sel_mask,
                          *, lsh_verification: bool = True,
                          block_r: int = 8, block_c: int = 512):
    """Streaming twin of `kernels.exchange.fused_exchange_streamed`
    (DESIGN.md §10): walks the SAME (R-tile, C-tile) grid with the same
    online max / log-sum-exp updates in the same order — the semantic
    reference for the streaming algorithm, and the CPU path for
    vocab-scale shapes the one-shot oracle cannot hold. Agreement with
    the kernel AND with `all_in_one_exchange_ref` is tolerance-bounded,
    not bitwise: the online softmax reorders the C reduction, the R
    means accumulate per tile, and XLA's fusion-dependent
    FMA/reassociation rewrites move the running accumulators by last
    ulps between compilation contexts. The §3.5 mask only flips on
    exact kl ties and is pinned equal in tests
    (tests/test_tiled_kernels.py)."""
    from repro.kernels.exchange import streamed_tiles

    m, n, r, c = neighbor_logits.shape
    br, pr, bc, pc = streamed_tiles(r, c, block_r, block_c)
    own_p = jnp.pad(own_logits.astype(jnp.float32), ((0, 0), (0, pr),
                                                     (0, pc)))
    nb_p = jnp.pad(neighbor_logits.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, pr), (0, pc)))
    y_p = jnp.pad(y_ref.astype(jnp.int32), ((0, 0), (0, pr)))
    nr, nc = (r + pr) // br, (c + pc) // bc

    l_acc = jnp.zeros((m, n), jnp.float32)
    kl_acc = jnp.zeros((m, n), jnp.float32)
    for ri in range(nr):
        m_nb = jnp.full((m, n, br), -jnp.inf)
        a_nb = jnp.zeros((m, n, br))
        g_nb = jnp.zeros((m, n, br))
        b_x = jnp.zeros((m, n, br))
        m_own = jnp.full((m, br), -jnp.inf)
        a_own = jnp.zeros((m, br))
        y_t = y_p[:, ri * br:(ri + 1) * br]
        for ci in range(nc):
            xo = own_p[:, ri * br:(ri + 1) * br, ci * bc:(ci + 1) * bc]
            xn = nb_p[:, :, ri * br:(ri + 1) * br, ci * bc:(ci + 1) * bc]
            col = ci * bc + jnp.arange(bc, dtype=jnp.int32)
            cvalid = col < c
            xo_m = jnp.where(cvalid, xo, -jnp.inf)
            xn_m = jnp.where(cvalid, xn, -jnp.inf)
            mo_new = jnp.maximum(m_own, jnp.max(xo_m, axis=-1))
            co = jnp.exp(m_own - mo_new)
            po = jnp.exp(xo_m - mo_new[..., None])
            a_own = a_own * co + jnp.sum(po, axis=-1)
            mn_new = jnp.maximum(m_nb, jnp.max(xn_m, axis=-1))
            cn = jnp.exp(m_nb - mn_new)
            a_nb = (a_nb * cn
                    + jnp.sum(jnp.exp(xn_m - mn_new[..., None]), axis=-1))
            b_x = (b_x * co[:, None]
                   + jnp.sum(po[:, None] * (xo[:, None] - xn), axis=-1))
            match = col[None, None, :] == y_t[:, :, None]
            g_nb = g_nb + jnp.sum(jnp.where(match[:, None], xn, 0.0),
                                  axis=-1)
            m_own, m_nb = mo_new, mn_new
        lse_nb = m_nb + jnp.log(a_nb)
        lse_own = m_own + jnp.log(a_own)
        rvalid = (ri * br + jnp.arange(br, dtype=jnp.int32)) < r
        nll = lse_nb - g_nb
        l_acc = l_acc + jnp.sum(jnp.where(rvalid, nll, 0.0), axis=-1)
        kl_r = b_x / a_own[:, None] - lse_own[:, None] + lse_nb
        kl_acc = kl_acc + jnp.sum(jnp.where(rvalid, kl_r, 0.0), axis=-1)

    l_ij = l_acc / float(r)
    sel_int = sel_mask.astype(jnp.int32)
    if lsh_verification:
        from repro.kernels.exchange import _upper_half_mask
        valid = _upper_half_mask(kl_acc / float(r), sel_int)
    else:
        valid = sel_mask.astype(bool)
    w = valid.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w, axis=-1), 1.0)
    target = (jnp.einsum("mn,mnrc->mrc", w, nb_p)
              / denom[:, None, None])[:, :r, :c]
    has_target = jnp.sum(w, axis=-1) > 0
    return l_ij, valid, target, has_target


def hamming_all_pairs_ref(codes_a, codes_b):
    """Oracle for hamming: broadcast XOR + SWAR popcount."""
    x = codes_a[:, None, :] ^ codes_b[None, :, :]
    return jnp.sum(popcount_u32(x), axis=-1)


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float = 0.0):
    """Oracle for flash_attention: naive softmax attention.
    q: (N, Sq, dh), k/v: (N, Sk, dh)."""
    import jax
    dh = q.shape[-1]
    scale = scale or dh ** -0.5
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nqk,nkd->nqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
