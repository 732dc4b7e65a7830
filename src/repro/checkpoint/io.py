"""Sharded checkpointing with async save, retention, and elastic restore.

Layout: <dir>/step_<N>/
    manifest.json          — tree structure, shapes, dtypes, step, mesh
    shard_<i>.npz          — flat param/opt arrays (chunked by size)
    _COMMITTED             — written last; restore ignores uncommitted dirs

Elastic restore: arrays are saved unsharded-logical (gathered); restoring
onto any device count / mesh re-shards from the logical view. For
multi-host deployments the same format is written per-process with
disjoint shard ownership — on this single-process container that
degenerates to one writer, which keeps tests honest but simple.
"""
from __future__ import annotations

import json
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

_SHARD_BYTES = 512 * 1024 * 1024


def _flatten(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save(ckpt_dir: str | Path, step: int, tree: Any, *,
         extra: Optional[dict] = None) -> Path:
    """Synchronous commit-marked save."""
    out = Path(ckpt_dir) / f"step_{step:08d}"
    tmp = out.with_suffix(".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    leaves, treedef = _flatten(tree)

    manifest = {"step": step, "treedef": str(treedef),
                "n_leaves": len(leaves),
                "extra": extra or {},
                "leaves": [], "shards": []}
    shard, shard_bytes, shard_idx = {}, 0, 0
    for i, leaf in enumerate(leaves):
        arr = np.asarray(jax.device_get(leaf))
        manifest["leaves"].append({"index": i, "shape": list(arr.shape),
                                   "dtype": str(arr.dtype),
                                   "shard": shard_idx})
        shard[f"leaf_{i}"] = arr
        shard_bytes += arr.nbytes
        if shard_bytes >= _SHARD_BYTES:
            np.savez(tmp / f"shard_{shard_idx}.npz", **shard)
            manifest["shards"].append(shard_idx)
            shard, shard_bytes, shard_idx = {}, 0, shard_idx + 1
    if shard:
        np.savez(tmp / f"shard_{shard_idx}.npz", **shard)
        manifest["shards"].append(shard_idx)
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    (tmp / "_COMMITTED").write_text(str(time.time()))
    if out.exists():
        shutil.rmtree(out)
    tmp.rename(out)
    return out


class AsyncSaver:
    """Overlap checkpoint I/O with training (one in flight at a time)."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[Path] = None

    def save(self, ckpt_dir, step, tree, extra=None):
        self.wait()
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 tree)

        def run():
            self.last_path = save(ckpt_dir, step, host_tree, extra=extra)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def _committed_step(p: Path) -> int | None:
    """The step of a committed ``step_XXXXXXXX`` directory, else None —
    an in-flight async save (``step_XXXXXXXX.tmp``, which holds its
    ``_COMMITTED`` marker just before the rename) is not a checkpoint."""
    _, _, num = p.name.partition("step_")
    if p.name.startswith("step_") and num.isdigit() \
            and (p / "_COMMITTED").exists():
        return int(num)
    return None


def latest_step(ckpt_dir: str | Path) -> Optional[int]:
    d = Path(ckpt_dir)
    if not d.exists():
        return None
    steps = [s for s in map(_committed_step, d.iterdir()) if s is not None]
    return max(steps) if steps else None


def restore(ckpt_dir: str | Path, step: int, like: Any,
            shardings: Any = None) -> Any:
    """Restore into the structure of ``like`` (elastic: any mesh/devices).

    If ``shardings`` (a matching tree of NamedSharding) is given, leaves
    are placed sharded with jax.device_put — this is the elastic-rescale
    path: the on-disk logical arrays re-shard onto the new topology.
    """
    src = Path(ckpt_dir) / f"step_{step:08d}"
    manifest = json.loads((src / "manifest.json").read_text())
    shards = {i: np.load(src / f"shard_{i}.npz")
              for i in manifest["shards"]}
    leaves_like, treedef = _flatten(like)
    assert len(leaves_like) == manifest["n_leaves"], \
        f"leaf count mismatch {len(leaves_like)} vs {manifest['n_leaves']}"
    out = []
    sh_leaves = (jax.tree.flatten(shardings)[0] if shardings is not None
                 else [None] * len(leaves_like))
    for meta, proto, sh in zip(manifest["leaves"], leaves_like, sh_leaves):
        arr = shards[meta["shard"]][f"leaf_{meta['index']}"]
        assert list(arr.shape) == list(proto.shape), \
            f"shape mismatch {arr.shape} vs {proto.shape}"
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jnp.asarray(arr, dtype=proto.dtype))
    return jax.tree.unflatten(treedef, out)


def retain(ckpt_dir: str | Path, keep: int = 3):
    """Delete all but the newest ``keep`` committed checkpoints."""
    d = Path(ckpt_dir)
    if not d.exists():
        return
    steps = sorted(s for s in map(_committed_step, d.iterdir())
                   if s is not None)
    for s in steps[:-keep]:
        shutil.rmtree(d / f"step_{s:08d}", ignore_errors=True)
