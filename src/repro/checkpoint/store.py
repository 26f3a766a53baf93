"""Pytree checkpointing to .npz (orbax-free, offline-friendly).

Layout: <dir>/step_<N>.npz with flattened key paths; tree structure is
reconstructed from the key paths on restore (dicts / tuples / lists).
"""
from __future__ import annotations

import os
import re
from typing import Any, List, Optional

import jax
import numpy as np

from repro import spans

_SEP = "/"


def _flatten(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_seg(p) for p in path)
        arr = np.asarray(leaf)  # analysis: host-ok — checkpointing IS the device->host pull
        spans.count(spans.HOST_PULLS)
        if arr.dtype.name == "bfloat16":     # npz can't serialize ml_dtypes
            arr = arr.astype(np.float32)     # (restore casts back per `like`)
        out[key] = arr
    return out


def _seg(p) -> str:
    if isinstance(p, jax.tree_util.DictKey):
        return f"d:{p.key}"
    if isinstance(p, jax.tree_util.SequenceKey):
        return f"s:{p.idx}"
    if isinstance(p, jax.tree_util.GetAttrKey):
        return f"a:{p.name}"
    return str(p)


def save(ckpt_dir: str, step: int, tree: Any, *,
         keep_last_k: Optional[int] = None) -> str:
    """Atomic snapshot; with `keep_last_k`, prune older step_*.npz AFTER
    the new file is durably in place (a continuously-running service
    would otherwise accumulate one snapshot per period forever). The
    newest k survive by step number; pruning never touches other files
    (e.g. the service's chain.json lives in the same directory)."""
    if keep_last_k is not None and keep_last_k < 1:
        raise ValueError(f"keep_last_k must be >= 1, got {keep_last_k}")
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    tmp = path + ".tmp.npz"          # .npz suffix so np.savez doesn't append
    np.savez(tmp, **_flatten(tree))  # analysis: host-ok — durable snapshot write
    os.replace(tmp, path)
    if keep_last_k is not None:
        steps = sorted(  # analysis: host-ok — int() parses filenames, not device values
            int(m.group(1)) for f in os.listdir(ckpt_dir)
            if (m := re.match(r"step_(\d+)\.npz$", f)))
        for old in steps[:-keep_last_k]:
            os.remove(os.path.join(ckpt_dir, f"step_{old:08d}.npz"))
    return path


def steps(ckpt_dir: str) -> List[int]:
    """All retained snapshot steps, ascending. The crash-safe resume
    path walks this list backwards: a truncated/corrupt newest file
    falls back to the previous retained snapshot."""
    if not os.path.isdir(ckpt_dir):
        return []
    # analysis: host-ok — int() parses snapshot filenames, not device values
    return sorted(int(m.group(1)) for f in os.listdir(ckpt_dir)
                  if (m := re.match(r"step_(\d+)\.npz$", f)))


def latest_step(ckpt_dir: str) -> Optional[int]:
    found = steps(ckpt_dir)
    return found[-1] if found else None


def restore(ckpt_dir: str, step: int, like: Any) -> Any:
    """Restore into the structure of ``like`` (a template pytree)."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    with np.load(path) as data:  # analysis: host-ok — snapshot file read
        flat = {k: data[k] for k in data.files}
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for path_keys, leaf in paths:
        key = _SEP.join(_seg(p) for p in path_keys)
        if key not in flat:
            raise KeyError(f"checkpoint missing {key}")
        arr = flat[key]
        if arr.shape != leaf.shape:
            raise ValueError(f"{key}: shape {arr.shape} != {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
