"""Personalized serving front (DESIGN.md §13).

WPFed's output is not ONE model — it is M personalized models, stacked
on the padded client axis of the live federation state. Serving them
individually (one forward per request against one client's params)
wastes the stacked layout; `PersonalizedServer` instead batches
requests ACROSS clients: gather the requested rows of the stacked
params, one vmapped forward over the whole batch. Requests for
different clients ride the same XLA program.

Static shapes meet variable load the same way churn meets the client
axis — padding. Batches pad up to a small ladder of bucket sizes, so
the server compiles once per bucket (not once per load level) and a
lone request does not retrace.

The server reads params by reference and `update_params` swaps them
between periods — the service driver serves period t's models while
period t+1 trains.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.privacy import declassifier, sink

DEFAULT_BUCKETS = (1, 4, 16, 64, 256)


@declassifier(
    name="served-logits", paper_eq="§2.1 (personalized model outputs)",
    justification="output of client i's OWN personalized model on the "
                  "requester's input — serving a client its own "
                  "predictions is the product of the federation, not a "
                  "cross-client disclosure")
def served_logits(logits):
    return logits


def _forward_fn(apply_fn: Callable, ps, ids, x):
    """The server's one XLA program: gather the requested client rows,
    then a single-example forward per request (vmapped) — cross-client
    batching in one call. Module-level (not a closure) so the taint
    verifier can trace exactly the jaxpr that serves
    (`analysis.taint.head_targets`, target "serving-forward")."""
    out = jax.vmap(
        lambda row, xi: apply_fn(row, xi[None])[0]
    )(jax.tree.map(lambda p: p[ids], ps), x)
    return sink("serving-response", served_logits(out))


class PersonalizedServer:
    """Batched inference over the federation's per-client models.

    apply_fn(params_i, x) -> logits — ONE client's forward over a batch
    of examples (the same contract as `core.protocol`). `params` is the
    stacked (M, ...) pytree from FedState.
    """

    def __init__(self, apply_fn: Callable, params: Any, *,
                 batch_buckets: Sequence[int] = DEFAULT_BUCKETS):
        if not batch_buckets or any(b < 1 for b in batch_buckets):
            raise ValueError(
                f"batch_buckets must be positive, got {batch_buckets!r}")
        self._buckets = tuple(sorted(set(int(b) for b in batch_buckets)))
        self._params = params
        self._num_clients = jax.tree.leaves(params)[0].shape[0]
        # one program, compiled once per bucket size (see _forward_fn)
        self._forward = jax.jit(functools.partial(_forward_fn, apply_fn))
        self._queue: List[Tuple[int, jnp.ndarray]] = []
        self.stats: Dict[str, Any] = {
            "requests": 0, "batches": 0, "padded_slots": 0,
            "total_s": 0.0, "latency_s": []}

    # -- request path ------------------------------------------------------
    def submit(self, client_id: int, x) -> int:
        """Enqueue one request (a single example for `client_id`'s
        personalized model). Returns its position in the next flush."""
        if not 0 <= client_id < self._num_clients:
            raise ValueError(
                f"client_id {client_id} outside the client axis "
                f"[0, {self._num_clients})")
        self._queue.append((int(client_id), jnp.asarray(x)))
        return len(self._queue) - 1

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def flush(self) -> List[np.ndarray]:  # analysis: host-ok (serving edge)
        """Serve every queued request; returns one logits array per
        request, in submit order. Oversized queues drain in
        largest-bucket chunks."""
        out: List[np.ndarray] = []
        while self._queue:
            chunk = self._queue[:self._buckets[-1]]
            del self._queue[:len(chunk)]
            out.extend(self._serve_chunk(chunk))
        return out

    def _serve_chunk(self, chunk):  # analysis: host-ok (request marshalling)
        n = len(chunk)
        b = self._bucket(n)
        ids = np.zeros((b,), np.int32)
        ids[:n] = [c for c, _ in chunk]
        x = jnp.stack([xi for _, xi in chunk])
        if b > n:  # pad to the bucket: same program for any load level
            x = jnp.concatenate(
                [x, jnp.zeros((b - n,) + x.shape[1:], x.dtype)])
        t0 = time.time()
        logits = self._forward(self._params, jnp.asarray(ids), x)
        logits = np.asarray(jax.block_until_ready(logits))
        dt = time.time() - t0
        self.stats["requests"] += n
        self.stats["batches"] += 1
        self.stats["padded_slots"] += b - n
        self.stats["total_s"] += dt
        self.stats["latency_s"].append(dt)
        return [logits[i] for i in range(n)]

    # -- federation integration -------------------------------------------
    def update_params(self, params: Any) -> None:
        """Hot-swap to a new period's personalized models. Shapes must
        match (the padded client axis is static — churn is masking)."""
        if jax.tree.leaves(params)[0].shape[0] != self._num_clients:
            raise ValueError("client axis changed; build a new server")
        self._params = params

    def throughput(self):  # analysis: host-ok (telemetry summarization)
        """Summary stats of the requests served so far."""
        lat = self.stats["latency_s"]
        total = max(self.stats["total_s"], 1e-9)
        return {
            "requests": float(self.stats["requests"]),
            "batches": float(self.stats["batches"]),
            "padded_slots": float(self.stats["padded_slots"]),
            "requests_per_s": self.stats["requests"] / total,
            "mean_batch_latency_s": float(np.mean(lat)) if lat else 0.0,
            "p50_latency_s": float(np.percentile(lat, 50)) if lat else 0.0,
            "p95_latency_s": float(np.percentile(lat, 95)) if lat else 0.0,
        }
