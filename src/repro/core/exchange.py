"""All-in-one reference-set exchange (WPFed Eq. 3 + §3.5 + Alg. 1's
distillation target — the paper's headline "single exchange" protocol).

The paper's contribution is that ONE reference-set logit exchange
simultaneously (1) transfers knowledge (the distillation target),
(2) evaluates model quality (the per-neighbor CE losses that feed the
Eq. 7 rankings), and (3) verifies similarity (§3.5's output-KL
upper-half filter). `all_in_one_exchange` is the single protocol entry
point for all three, mirroring `core.neighbor.select_partners` for the
selection subsystem (DESIGN.md §7):

  "kernel" -> fused Pallas kernel (one shared neighbor log-softmax
              while the (N, R, C) tile is in VMEM; interpret off-TPU),
  "oracle" -> the bit-exact jnp twin (ref.all_in_one_exchange_ref),
  "auto"   -> kernel on TPU, oracle elsewhere.

`FedConfig.exchange_tiling` layers the VMEM regime on top (DESIGN.md
§10): "oneshot" is the bit-exact default above; "tiled" streams
R/C-tiled blocks with an online softmax (vocab-scale reference sets —
tolerance-bounded, §3.5 mask preserved); "auto" picks from the
explicit per-program VMEM estimate (`backends.exchange_vmem_bytes`)
instead of OOMing. On the oracle backend "tiled" selects the streaming
jnp twin (`ref.streamed_exchange_ref`) — the CPU path for shapes the
one-shot oracle cannot materialize.

The unfused pieces (`distill.cross_entropy`,
`verify.lsh_verification_mask`, `distill.aggregate_neighbor_outputs`)
remain the semantic reference — tests assert both one-shot fused paths
match their composition bit-exactly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.privacy import declassifier
from repro.core import backends
from repro.kernels import ref
from repro.kernels.exchange import fused_exchange, fused_exchange_streamed


@declassifier(
    name="public-ref-logits", paper_eq="Eq. 2-3 (§3.1 logit exchange)",
    justification="the paper's designated exchange artifact: neighbor "
                  "outputs on the (public or mutually shared) reference "
                  "set — the knowledge-transfer channel the protocol "
                  "defines as releasable in place of raw parameters")
def public_ref_logits(neighbor_logits):
    """Mark a (M, N, R, C) neighbor-logit web as the exchanged artifact.

    `core.protocol.exchange_phase` routes every logit web through this
    identity before it enters the exchange: the taint verifier treats
    the gathered logits as disclosed-by-design (DESIGN.md §14), so the
    rest of the round is proven clean DOWNSTREAM of exactly this one
    sanctioned release."""
    return neighbor_logits


class ExchangeResult(NamedTuple):
    """Everything one reference-set exchange yields, for all M clients."""
    l_ij: jnp.ndarray        # (M, N) f32 — Eq. 3 CE of neighbor j on X_i^ref
    valid_mask: jnp.ndarray  # (M, N) bool — §3.5 survivors (selected & upper half)
    target_ref: jnp.ndarray  # (M, R, C) f32 — masked mean of valid neighbor logits
    has_target: jnp.ndarray  # (M,) bool — any neighbor passed (else zeros target)


def all_in_one_exchange(own_logits, neighbor_logits, y_ref, sel_mask, fed,
                        *, backend: str | None = None,
                        tiling: str | None = None) -> ExchangeResult:
    """Distill + evaluate + verify in one pass over the exchanged logits.

    own_logits: (M, R, C) — each client's outputs on its reference set;
    neighbor_logits: (M, N, R, C) — the selected neighbors' outputs on
    that same set (gathered, DESIGN.md §3); y_ref: (M, R) int labels;
    sel_mask: (M, N) bool selected slots; fed: FedConfig (consumes
    lsh_verification, exchange_backend and exchange_tiling).
    `backend` / `tiling` override the FedConfig fields when given.

    The tiling regime resolves from the explicit one-shot VMEM
    estimate (`backends.resolve_tiling`, DESIGN.md §10): shapes whose
    (BM, N, R, C) tile fits the budget keep the bit-exact one-shot
    path; beyond it the streamed R/C-tiled path runs (tolerance-bounded
    l_ij/target, identical §3.5 mask off exact kl ties).

    With fed.lsh_verification=False the §3.5 filter is skipped and
    valid_mask == sel_mask (the "w/o verification" ablation).
    """
    m, n = sel_mask.shape
    if n == 0:                         # degenerate M <= 1 federation
        r, c = own_logits.shape[-2:]
        return ExchangeResult(
            jnp.zeros((m, 0), jnp.float32), jnp.zeros((m, 0), bool),
            jnp.zeros((m, r, c), jnp.float32), jnp.zeros((m,), bool))
    r, c = neighbor_logits.shape[-2:]
    resolved = backends.resolve(backend or fed.exchange_backend)
    resolved_tiling = backends.resolve_tiling(
        tiling or fed.exchange_tiling,
        backends.exchange_vmem_bytes(n, r, c))
    if resolved == "kernel":
        exchange_fn = (fused_exchange_streamed
                       if resolved_tiling == "tiled" else fused_exchange)
        out = exchange_fn(own_logits, neighbor_logits, y_ref, sel_mask,
                          lsh_verification=fed.lsh_verification)
    elif resolved_tiling == "tiled":
        out = ref.streamed_exchange_ref(
            own_logits, neighbor_logits, y_ref, sel_mask,
            lsh_verification=fed.lsh_verification)
    else:
        out = ref.all_in_one_exchange_ref(
            own_logits, neighbor_logits, y_ref, sel_mask,
            lsh_verification=fed.lsh_verification)
    return ExchangeResult(*out)
