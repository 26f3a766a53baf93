"""Work the algorithm needs, counted from shapes: client-model FLOPs
per period and the operations and bytes of the LSH and exchange
kernels. Whatever implements the work, these are the counts its time
is measured against; recomputed or padded work does not count.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

# operations per element of the (M, N, R, C) neighbor-logit web in the
# exchange: log-softmax (max, subtract, exp, sum, log: 5), the KL term
# p_own * (log p_own - log p_nb) summed (3), the masked target sum (2)
EXCHANGE_FLOPS_PER_ELEMENT = 10


def peaks(device_kind: str) -> dict:
    """The table's row for this device; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def round_flops(forward_flops: int, m: int, n: int, fed: dict, wl: dict,
                public: bool, train_flops: int | None = None) -> int:
    """Client-model FLOPs of one round: the local update's forward and
    backward over each step's batch and the reference rows
    (`train_flops` an example where given, as for a frozen base that
    takes no weight gradients, else 3 forward costs), the exchange's
    forwards (M*N*R personal, M*R public, plus each client's own M*R
    when personal) and the evaluation."""
    r = wl["ref_rows"]
    batch = min(fed["local_batch"], wl["train_rows"])
    train = 3 * forward_flops if train_flops is None else train_flops
    update = m * fed["local_steps"] * (batch + r) * train
    exchange = m * r if public else m * n * r + m * r
    evaluate = m * wl["test_rows"]
    return update + forward_flops * (exchange + evaluate)


def lsh_work(m: int, p: int, bits: int) -> tuple:
    """(FLOPs, bytes) of hashing M parameter vectors of length P: the
    projection matmul, the parameters read once, the codes written."""
    return 2 * m * p * bits, m * p * 4 + m * bits // 8


def exchange_work(m: int, n: int, r: int, c: int) -> tuple:
    """(FLOPs, bytes) of one exchange: the neighbor web, own logits and
    labels read, the target, l_ij and the mask written."""
    flops = EXCHANGE_FLOPS_PER_ELEMENT * m * n * r * c
    reads = 4 * (m * n * r * c + m * r * c + m * r)
    writes = 4 * m * r * c + m * n * (4 + 1) + m
    return flops, reads + writes


def min_seconds(flops: float, nbytes: float, pk: dict) -> tuple:
    """The least time the chip could take and which bound sets it."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
