"""Checkpointing: sharded, async, elastic.

Layout: ``<dir>/step_<N>/`` with one ``.npy`` per flattened pytree leaf and a
``manifest.json`` (tree structure, dtypes, step, data index, mesh shape).
Writes go to a temp dir then atomically rename — a preempted writer never
corrupts the latest checkpoint; readers pick the newest *complete* step.

* **async** — ``save_async`` snapshots to host memory (device_get) then
  writes on a background thread; training continues immediately.
* **elastic resharding** — restore() takes the *target* mesh/shardings: leaves
  are loaded from full host arrays and re-placed with jax.device_put, so a
  run checkpointed on a 1-pod mesh restores cleanly onto a 2-pod mesh (and
  vice versa). Tested in tests/test_checkpoint.py via device-count subprocess.
* **preemption** — train loop installs a SIGTERM handler that flags a final
  synchronous save (dist/fault.py).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

PyTree = Any


def _leaf_paths(tree) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(_pstr(p) for p in path)
        out[key] = leaf
    return out


def _pstr(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def save(ckpt_dir: str, step: int, tree: PyTree,
         extra: Optional[Dict] = None) -> str:
    """Synchronous atomic save."""
    leaves = _leaf_paths(tree)
    host = {k: np.asarray(jax.device_get(v)) for k, v in leaves.items()}
    return _write(ckpt_dir, step, host, jax.tree.structure(tree), extra)


def save_async(ckpt_dir: str, step: int, tree: PyTree,
               extra: Optional[Dict] = None) -> threading.Thread:
    """Snapshot to host now, write in background; returns the writer thread."""
    leaves = _leaf_paths(tree)
    host = {k: np.asarray(jax.device_get(v)) for k, v in leaves.items()}
    structure = jax.tree.structure(tree)
    t = threading.Thread(
        target=_write, args=(ckpt_dir, step, host, structure, extra),
        daemon=True)
    t.start()
    return t


def _write(ckpt_dir, step, host_leaves, structure, extra):
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    # unique tmp per writer: concurrent writers of the same step (async
    # periodic save racing a final synchronous save) must not share a dir
    tmp = final + f".tmp{os.getpid()}_{threading.get_ident()}"
    os.makedirs(tmp, exist_ok=True)
    names = {}
    for i, (key, arr) in enumerate(sorted(host_leaves.items())):
        fname = f"leaf_{i:05d}.npy"
        np.save(os.path.join(tmp, fname), arr)
        names[key] = fname
    manifest = {
        "step": step,
        "leaves": names,
        "treedef": str(structure),
        "extra": extra or {},
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    try:
        os.rename(tmp, final)
    except OSError:
        # another writer completed the same step first; ours is redundant
        shutil.rmtree(tmp, ignore_errors=True)
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", d)
        if m and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def restore(ckpt_dir: str, template: PyTree, step: Optional[int] = None,
            shardings: Optional[PyTree] = None
            ) -> Tuple[PyTree, Dict]:
    """Restore into the structure of ``template``; optionally place each leaf
    with the given shardings (elastic resharding onto any mesh)."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    names = manifest["leaves"]
    keys = _leaf_paths(template)
    shard_leaves = _leaf_paths(shardings) if shardings is not None else {}
    out = {}
    for key, tmpl_leaf in keys.items():
        arr = np.load(os.path.join(d, names[key]))
        if hasattr(tmpl_leaf, "dtype"):
            arr = arr.astype(tmpl_leaf.dtype)
        if key in shard_leaves:
            out[key] = jax.device_put(arr, shard_leaves[key])
        else:
            out[key] = jax.numpy.asarray(arr)
    restored = jax.tree_util.tree_unflatten(
        jax.tree.structure(template), [out[k] for k in keys])
    return restored, manifest["extra"]
