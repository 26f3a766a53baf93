"""The comparison that decides `correct`: what the timed path produced
in its first periods against the plain reference (reference.py) run
from the same seed.

Numbers, each a worst case over the periods compared:
  loss_gap     |loss - ref| / |ref| of the mean client loss that each
               period's last round reports
  acc_gap      |acc - ref| of the mean test accuracy reported with it
  grad_gap     Adam's first moment after the first period, the
               gradients as the optimizer got them: per leaf
               | |m| - |m_ref| | / max(|m_ref|, median leaf |m_ref|)
  change_gap   the same gap of |params - initial params| after the
               last period compared
  code_bits    share of announced LSH code bits that differ
  rank_differ  share of revealed ranking entries (selected neighbor
               ids ordered by l_ij) that differ
  ledger       1 if the host ledger fails verification or its last
               block does not hold the state's codes and rankings
  ckpt         1 if the last checkpoint written does not restore to
               the state it was written from
Leaves whose reference first moment is under a thousandth of the
median leaf's move by rounding alone and are left out of grad_gap and
change_gap.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NEGLIGIBLE = 1e-3


def norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(x, np.float64).ravel())
                     for x in leaves])


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray) -> float:
    scale = np.maximum(ref, np.median(ref))
    return float(np.max((np.abs(prog - ref) / scale)[keep]))


def bit_share(a: List[np.ndarray], b: List[np.ndarray]) -> float:
    worst = 0.0
    for x, y in zip(a, b):
        xor = np.bitwise_xor(np.asarray(x, np.uint32),
                             np.asarray(y, np.uint32))
        bits = np.unpackbits(np.ascontiguousarray(xor).view(np.uint8))
        worst = max(worst, float(bits.mean()))
    return worst


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """prog and ref each hold: loss and acc (one per period), codes and
    rankings (one array per period), m (first moment leaves after the
    first period) and delta (parameter change leaves after the last)."""
    m_ref = norms(ref["m"])
    keep = m_ref >= NEGLIGIBLE * np.median(m_ref)
    out = {
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(prog["loss"], ref["loss"])),
        "acc_gap": max(abs(p - r) for p, r in zip(prog["acc"], ref["acc"])),
        "grad_gap": worst_leaf_gap(norms(prog["m"]), m_ref, keep),
        "change_gap": worst_leaf_gap(norms(prog["delta"]),
                                     norms(ref["delta"]), keep),
        "code_bits": bit_share(prog["codes"], ref["codes"]),
        "rank_differ": max(float(np.mean(np.asarray(p) != np.asarray(r)))
                           for p, r in zip(prog["rankings"],
                                           ref["rankings"])),
    }
    return {k: float(v) for k, v in out.items()}


def judge(found: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}} for every number with a limit."""
    return {k: {"value": found[k], "limit": limits[k]}
            for k in limits if k in found}
