"""Versioned checkpoint store with PANIGRAHAM-style snapshot validation.

The graph journal (``resil/journal.py``) stores its base snapshots here:

  * every save commits a new **version** (monotonic counter) and writes the
    manifest LAST, atomically (tmp + rename) — a manifest is the analogue of
    a committed graph state; leaves written before the manifest rename are
    invisible, like nodes CAS-linked but not yet reachable;
  * a restore performs the paper's **double collect**: read manifest ->
    load leaves -> re-read manifest; if the version moved, a concurrent
    writer raced the read and the restore retries.  The loaded tree is thus
    a *validated consistent snapshot* even with an async writer — exactly
    SCAN/CMPTREE on files;
  * per-leaf checksums play the role of ``ecnt``: a leaf rewritten in place
    between the two manifest reads is detected even if the version check is
    defeated (e.g. clock-skewed writers on shared storage).

**Elastic resharding**: leaves are stored as full (unsharded) arrays keyed by
tree path; ``restore_checkpoint(..., mesh, specs)`` re-places them under ANY
mesh/sharding — restoring a snapshot taken on one device layout onto
another is a restore, not a migration.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return "/".join(out) or "_root"


def _leaf_files(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {(_path_str(path)): leaf for path, leaf in leaves}


def _checksum(arr: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


def save_checkpoint(ckpt_dir: str, step: int, tree, *, version: int,
                    verify: bool = False, extra: Optional[dict] = None) -> dict:
    """Write one checkpoint; returns the manifest.

    ``extra`` is an optional JSON-serializable dict stored verbatim in the
    manifest (and thus committed atomically with it) — side-car state that
    must travel with the snapshot, e.g. learned serving thresholds.
    """
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    os.makedirs(d, exist_ok=True)
    manifest = {"step": step, "version": version, "leaves": {},
                "time": time.time()}
    if extra:
        manifest["extra"] = extra
    for name, leaf in _leaf_files(tree).items():
        arr = np.asarray(jax.device_get(leaf))
        fn = name.replace("/", ".") + ".npy"
        np.save(os.path.join(d, fn), arr)
        entry = {"file": fn, "shape": list(arr.shape), "dtype": str(arr.dtype)}
        if verify:
            entry["sha1"] = _checksum(arr)
        manifest["leaves"][name] = entry
    # manifest last + atomic rename = the commit point (linearization point)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, os.path.join(d, "manifest.json"))
    _update_index(ckpt_dir, step, version)
    return manifest


def _update_index(ckpt_dir: str, step: int, version: int) -> None:
    idx_path = os.path.join(ckpt_dir, "index.json")
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump({"latest_step": step, "version": version}, f)
    os.replace(tmp, idx_path)


def latest_step(ckpt_dir: str) -> Optional[int]:
    idx_path = os.path.join(ckpt_dir, "index.json")
    if not os.path.exists(idx_path):
        return None
    with open(idx_path) as f:
        return json.load(f)["latest_step"]


def _read_manifest(ckpt_dir: str, step: int) -> dict:
    with open(os.path.join(ckpt_dir, f"step_{step:08d}", "manifest.json")) as f:
        return json.load(f)


def read_manifest(ckpt_dir: str, step: int) -> dict:
    """The committed manifest of one step (the atomically-renamed file)."""
    return _read_manifest(ckpt_dir, step)


def restore_checkpoint(ckpt_dir: str, step: int, tree_like, *,
                       mesh: Optional[Mesh] = None, specs=None,
                       verify: bool = False, max_retries: int = 8):
    """Double-collect validated restore; reshards onto ``mesh``/``specs``.

    ``tree_like`` supplies the pytree structure (arrays or SDS).
    """
    for _ in range(max_retries):
        m1 = _read_manifest(ckpt_dir, step)
        d = os.path.join(ckpt_dir, f"step_{step:08d}")
        loaded = {}
        ok = True
        for name, entry in m1["leaves"].items():
            arr = np.load(os.path.join(d, entry["file"]))
            if verify and "sha1" in entry and _checksum(arr) != entry["sha1"]:
                ok = False          # leaf changed under us (ecnt mismatch)
                break
            loaded[name] = arr
        m2 = _read_manifest(ckpt_dir, step)
        if ok and m2["version"] == m1["version"]:
            break                    # CMPTREE matched: consistent snapshot
    else:
        raise RuntimeError("checkpoint kept changing during restore")

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    spec_leaves = None
    if specs is not None:
        spec_leaves = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda s: isinstance(s, P))[0]
    out = []
    for i, (path, like) in enumerate(flat):
        arr = loaded[_path_str(path)].astype(like.dtype)
        if mesh is not None and spec_leaves is not None:
            arr = jax.device_put(arr, NamedSharding(mesh, spec_leaves[i]))
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)

