"""Backend & tiling resolution for kernel-backed protocol subsystems.

`FedConfig` carries one backend field per kernel-backed subsystem
(`selection_backend`, `exchange_backend`); both accept the same three
values and resolve through this single helper so the string validation
lives in exactly one place (DESIGN.md §4, §7):

  "kernel" -> the Pallas kernel path (interpret-mode off-TPU — the
              correctness path, not a CPU speedup),
  "oracle" -> the bit-exact pure-jnp twin,
  "auto"   -> kernel on TPU, oracle elsewhere.

Each kernel-backed subsystem additionally carries a *tiling* field
(`selection_tiling`, `exchange_tiling`) resolved by `resolve_tiling`
(DESIGN.md §10):

  "oneshot" -> the original kernels that hold their full working set
               per program (bit-exact defaults; VMEM is O(problem)),
  "tiled"   -> the VMEM-tiled streaming kernels (selection: column-
               tiled two-pass top-N, bit-exact; exchange: R/C-tiled
               online-softmax, tolerance-bounded — see §10),
  "auto"    -> oneshot while the per-program working set fits the VMEM
               budget, tiled beyond it — an explicit estimate
               (`selection_vmem_bytes` / `exchange_vmem_bytes`)
               instead of an OOM at lowering time.

This module deliberately imports only jax and the jax-only
`repro.kernels` package init. `repro.core` modules import it directly;
`repro.kernels.ops.resolve_backend` delegates here via a function-level
import (`repro.core.__init__` pulls in the whole protocol, so a
module-level import from the kernels package would be a cycle).
"""
from __future__ import annotations

import jax

from repro.kernels import resolve_interpret

BACKENDS = ("auto", "kernel", "oracle")
TILINGS = ("auto", "oneshot", "tiled")
# selection additionally accepts "ann" (DESIGN.md §11): the
# sub-quadratic LSH-bucket candidate index. Exchange has no ANN
# analogue, so plain `resolve` keeps rejecting it.
SELECTION_BACKENDS = BACKENDS + ("ann",)

# "auto" hands selection to the ANN path only when the exact kernel's
# FLOPs exceed the candidate path's by this ratio AND the federation
# is past the floor — below it the exact kernels are comfortably
# VMEM/FLOP-bounded and stay bit-exact for free.
ANN_AUTO_MIN_M = 4096
ANN_AUTO_MIN_RATIO = 4.0

# TPU v5e VMEM is ~16 MiB/core; the budget leaves headroom for the
# compiler's own double-buffering and spills (DESIGN.md §10).
VMEM_LIMIT_BYTES = 16 * 2 ** 20
VMEM_BUDGET_BYTES = int(VMEM_LIMIT_BYTES * 0.75)


def interpret() -> bool:
    """Pallas kernels run in interpret mode everywhere but TPU (the
    rule every kernel entry point applies when no `interpret` is
    given)."""
    return resolve_interpret()


def _reject(field: str, value, accepted) -> ValueError:
    """The one rejection formatter for every backend/tiling string
    (DESIGN.md §12): the message always names the offending FIELD, the
    offending value, and the accepted set, in this exact shape — the
    property test in tests/test_analysis.py asserts on it."""
    return ValueError(
        f"unknown {field}: {value!r} (expected one of {tuple(accepted)})")


def resolve(backend: str) -> str:
    """Validate and resolve a backend string to "kernel" or "oracle"."""
    if backend == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "oracle"
    if backend not in ("kernel", "oracle"):
        raise _reject("backend", backend, BACKENDS)
    return backend


# ---------------------------------------------------------------------------
# per-program VMEM estimates (DESIGN.md §10 carries the derivations)
# ---------------------------------------------------------------------------
def selection_vmem_bytes(m: int, bits_tot: int, *, block_m: int = 8) -> int:
    """One-shot `fused_select` working set per program: unpacked +-1
    row/column codes ((BM + M) * bits) + the (BM, M) weight block, f32,
    plus the packed uint32 inputs."""
    words = bits_tot // 32
    unpacked = (block_m + m) * bits_tot * 4
    weights = block_m * m * 4
    packed = (block_m + m) * words * 4
    return unpacked + weights + packed


def selection_tiled_vmem_bytes(bits_tot: int, *, block_m: int = 128,
                               block_k: int = 512, nsel: int = 16) -> int:
    """Column-tiled `fused_select_tiled` working set per program:
    O(tile), independent of M — unpacked (BM + BK) codes, the (BM, BK)
    weight tile, and the (BM, N) running top-N scratch."""
    unpacked = (block_m + block_k) * bits_tot * 4
    weights = block_m * block_k * 4
    scratch = 2 * block_m * max(nsel, 1) * 4
    return unpacked + weights + scratch


def exchange_vmem_bytes(n: int, r: int, c: int, *, block_m: int = 8) -> int:
    """One-shot `fused_exchange` working set per program: the
    (BM, N, R, C) neighbor-logit tile plus the (BM, R, C) own tile and
    the (BM, R, C) target output, f32."""
    return block_m * (n + 2) * r * c * 4


def exchange_tiled_vmem_bytes(n: int, *, block_m: int = 8, block_r: int = 8,
                              block_c: int = 512) -> int:
    """Streamed `fused_exchange_streamed` working set per program:
    O(tile) — the (BM, N, BR, BC) neighbor tile, the (BM, BR, BC) own
    tile, and the online-softmax scratch (4 arrays of (BM, N, BR) plus
    2 of (BM, BR))."""
    tiles = block_m * (n + 1) * block_r * block_c * 4
    scratch = (4 * block_m * n * block_r + 2 * block_m * block_r) * 4
    return tiles + scratch


def ann_vmem_bytes(bits_tot: int, *, block_m: int = 8,
                   block_k: int = 256, nsel: int = 16) -> int:
    """`fused_select_ann` working set per program: unpacked +-1 row
    codes (BM * bits) and candidate codes (BM * BK * bits), the
    (BM, BK) weight tile, and the (BM, N) running top-N scratch."""
    unpacked = (block_m + block_m * block_k) * bits_tot * 4
    weights = block_m * block_k * 4
    scratch = 2 * block_m * max(nsel, 1) * 4
    return unpacked + weights + scratch


# Introspection hook for the static-analysis gate (DESIGN.md §12):
# every estimator that a kernel contract can declare by name. The
# `repro.analysis` kernel-contract checker cross-validates each one
# against the VMEM bytes implied by the kernel's actual BlockSpecs, so
# a kernel retune that forgets this file fails CI instead of silently
# skewing resolve_tiling's "auto" decision.
VMEM_ESTIMATORS = {
    "selection_vmem_bytes": selection_vmem_bytes,
    "selection_tiled_vmem_bytes": selection_tiled_vmem_bytes,
    "exchange_vmem_bytes": exchange_vmem_bytes,
    "exchange_tiled_vmem_bytes": exchange_tiled_vmem_bytes,
    "ann_vmem_bytes": ann_vmem_bytes,
}


# ---------------------------------------------------------------------------
# per-round FLOP estimates — the "auto" exact-vs-ann decision (§11)
# ---------------------------------------------------------------------------
def selection_flops(m: int, bits_tot: int) -> float:
    """Exact selection prices every pair: one M x M +-1 Gram matmul,
    2 * M^2 * bits FLOPs per round (tiling changes VMEM, not FLOPs)."""
    return 2.0 * m * m * bits_tot


def ann_selection_flops(m: int, bits_tot: int, k: int) -> float:
    """ANN selection prices only candidates: 2 * M * K * bits, with
    K = (probes + 1) * bucket_cap + teaser (core.ann.candidate_count)."""
    return 2.0 * m * k * bits_tot


def resolve_selection(backend: str, m: int, *, exact_flops: float,
                      ann_flops: float) -> str:
    """Resolve a selection backend to "kernel", "oracle", or "ann".

    "ann" is explicit opt-in at any M. "auto" additionally routes to
    the ANN path once the federation is big enough that the exact
    Gram is ANN_AUTO_MIN_RATIO x the candidate path's FLOPs AND
    m >= ANN_AUTO_MIN_M — below either threshold "auto" keeps the
    bit-exact §10 kernels (approximation is never silent at small M).
    """
    if backend == "ann":
        return "ann"
    if backend == "auto":
        if m >= ANN_AUTO_MIN_M and exact_flops >= ANN_AUTO_MIN_RATIO * \
                ann_flops:
            return "ann"
        return resolve("auto")
    if backend not in ("kernel", "oracle"):
        raise _reject("selection backend", backend, SELECTION_BACKENDS)
    return backend


def resolve_tiling(tiling: str, est_oneshot_bytes: int, *,
                   budget_bytes: int = None) -> str:
    """Validate and resolve a tiling string to "oneshot" or "tiled".

    "auto" compares the one-shot kernel's per-program VMEM estimate
    against the budget — the explicit form of the decision that used to
    be an OOM at M ~ 10^4 clients / vocab-scale reference sets."""
    if tiling == "auto":
        budget = VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
        return "oneshot" if est_oneshot_bytes <= budget else "tiled"
    if tiling not in ("oneshot", "tiled"):
        raise _reject("tiling", tiling, TILINGS)
    return tiling
