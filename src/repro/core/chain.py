"""Blockchain announcement layer (WPFed §2.2, §3.6).

Two tiers, by design:

1. **Host ledger** (this module): an append-only hash-chained block list
   with SHA-256 commitments — the auditable record. One block per round
   holds every client's announcement a_i = {lsh_i, C_i} plus last
   round's reveals. ``verify_chain`` re-hashes the whole chain;
   ``verify_reveal`` checks commit-and-reveal (Eq. 9-10).

2. **In-graph commitments** (``fnv1a_commit``): a JAX-traceable 64-bit
   FNV-1a hash over ranking integers so the *protocol step itself*
   (jit/vmap'd across clients) can verify reveals without host sync.
   SHA-256 remains the on-chain binding commitment; the FNV path is the
   fast-path filter inside the training loop. Both are computed over the
   same canonical serialization, and tests pin them to each other's
   accept/reject decisions.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.analysis.privacy import declassifier


# ---------------------------------------------------------------------------
# canonical serialization + commitments
# ---------------------------------------------------------------------------
def canonical_ranking_bytes(ranking) -> bytes:
    """Rankings are int vectors (neighbor ids, best first; -1 padding)."""
    # analysis: host-ok — the ledger hashes host bytes by design (§8)
    arr = np.asarray(ranking, np.int64)
    return arr.tobytes() + arr.shape.__repr__().encode()


def sha256_commit(ranking, salt: int = 0) -> str:
    h = hashlib.sha256()
    h.update(salt.to_bytes(8, "little", signed=False))
    h.update(canonical_ranking_bytes(ranking))
    return h.hexdigest()


_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)


@declassifier(
    name="commitment", paper_eq="Eq. 9-10 (§3.6 commit-and-reveal)",
    justification="a one-way hash of an already-releasable ranking "
                  "vector: binding for the reveal check, disclosing "
                  "nothing beyond the ranking it commits to")
def fnv1a_commit(ranking, salt=0):
    """JAX-traceable commitment over the same canonical int sequence.

    ranking: (..., N) int32 -> (...,) uint64-as-uint32-pair packed into
    a single uint32 (upper xor lower) — collision-resistant enough for
    the in-graph fast path; the binding commitment is SHA-256 on chain.
    """
    r = jnp.asarray(ranking).astype(jnp.uint32)
    salt = jnp.asarray(salt, jnp.uint32)
    h = jnp.full(r.shape[:-1], 2166136261, jnp.uint32) ^ salt

    def body(h, x):
        # FNV-1a over the 4 bytes of each int
        for shift in (0, 8, 16, 24):
            byte = (x >> jnp.uint32(shift)) & jnp.uint32(0xFF)
            h = (h ^ byte) * jnp.uint32(16777619)
        return h

    for idx in range(r.shape[-1]):
        h = body(h, r[..., idx])
    return h


# ---------------------------------------------------------------------------
# host ledger
# ---------------------------------------------------------------------------
@dataclass
class Block:
    index: int
    prev_hash: str
    payload: Dict[str, Any]            # round announcements + reveals
    # 0.0 = "unstamped" (genesis); publish_round stamps wall-clock time
    timestamp: float = field(default_factory=lambda: 0.0)
    hash: str = ""

    def compute_hash(self) -> str:
        h = hashlib.sha256()
        h.update(self.prev_hash.encode())
        h.update(str(self.index).encode())
        h.update(repr(self.timestamp).encode())
        h.update(json.dumps(self.payload, sort_keys=True,
                            default=str).encode())
        return h.hexdigest()


class Blockchain:
    """Append-only announcement ledger shared by all clients."""

    def __init__(self):
        genesis = Block(0, "0" * 64, {"genesis": True})
        genesis.hash = genesis.compute_hash()
        self.blocks: List[Block] = [genesis]

    def publish_round(self, round_idx: int,
                      announcements: Dict[int, Dict[str, Any]],
                      reveals: Optional[Dict[int, Any]] = None) -> Block:
        """announcements: client_id -> {"lsh": hex, "commit": sha256hex}
        reveals: client_id -> ranking list (for round_idx - 1)."""
        payload = {
            "round": round_idx,
            "announcements": {str(k): v for k, v in announcements.items()},
            "reveals": {str(k): list(map(int, v))
                        for k, v in (reveals or {}).items()},
        }
        blk = Block(len(self.blocks), self.blocks[-1].hash, payload,
                    timestamp=time.time())
        blk.hash = blk.compute_hash()
        self.blocks.append(blk)
        return blk

    def verify_chain(self) -> bool:
        for i in range(1, len(self.blocks)):
            b = self.blocks[i]
            if b.prev_hash != self.blocks[i - 1].hash:
                return False
            if b.hash != b.compute_hash():
                return False
        return True

    def head_round(self) -> int:  # analysis: host-ok — int() on ledger JSON payloads, not device values
        """Highest round index on chain; -1 for a genesis-only ledger.
        The resume path compares this against the checkpoint's round
        counter to catch silent ledger rollback (transport.py)."""
        for b in reversed(self.blocks):
            r = b.payload.get("round")
            if r is not None:
                return int(r)
        return -1

    def round_block(self, round_idx: int) -> Optional[Block]:
        for b in reversed(self.blocks):
            if b.payload.get("round") == round_idx:
                return b
        return None

    # -- durable form (the service's kill/resume path, DESIGN.md §13) --
    def to_json(self) -> str:
        """Full ledger as canonical JSON. The stored hashes are the
        ORIGINAL ones — verify_chain recomputes over the deserialized
        payloads, so a tampered file fails verification after load
        instead of laundering fresh hashes."""
        return json.dumps([{
            "index": b.index, "prev_hash": b.prev_hash,
            "payload": b.payload, "timestamp": b.timestamp,
            "hash": b.hash,
        } for b in self.blocks], sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "Blockchain":
        chain = cls.__new__(cls)
        chain.blocks = [
            Block(d["index"], d["prev_hash"], d["payload"],
                  timestamp=d["timestamp"], hash=d["hash"])
            for d in json.loads(text)]
        if not chain.blocks:
            raise ValueError("serialized chain has no genesis block")
        return chain


def verify_reveal(commitment_hex: str, revealed_ranking, salt: int = 0) -> bool:
    """Eq. (10): recompute the hash of the revealed ranking."""
    return sha256_commit(revealed_ranking, salt) == commitment_hex


def lsh_code_hex(code) -> str:
    # analysis: host-ok — announcement serialization for the host ledger
    return np.asarray(code, np.uint32).tobytes().hex()


def encode_announcements(codes, rankings, announcing=None
                         ) -> Tuple[Dict[int, Dict[str, str]],
                                    Dict[int, List[int]]]:
    """The ledger entries of one reselection, from host arrays:
    `announcements[i]` = a_i = {"lsh": code hex, "commit": SHA-256 of
    the ranking} and `reveals[i]` = the ranking as ints, for every
    client i, or only where the (M,) bool `announcing` is set."""
    ids = [i for i in range(len(codes))
           if announcing is None or announcing[i]]
    announcements = {i: {"lsh": lsh_code_hex(codes[i]),
                         "commit": sha256_commit(rankings[i])}
                     for i in ids}
    reveals = {i: rankings[i].tolist() for i in ids}
    return announcements, reveals


def save_chain(path: str, chain: Blockchain) -> str:
    """Atomically persist the ledger (tmp + os.replace, the
    checkpoint.store discipline: a crash mid-write never truncates the
    previous good file)."""
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(chain.to_json())
    os.replace(tmp, path)
    return path


def load_chain(path: str) -> Blockchain:
    """Restore a persisted ledger. Integrity is the caller's call to
    `verify_chain()` — the service driver refuses to resume without it."""
    with open(path, "r", encoding="utf-8") as fh:
        return Blockchain.from_json(fh.read())
