"""Flat-file checkpoint store with a pytree manifest.

Layout:  <dir>/step_<n>/manifest.json + one ``.npy`` per leaf.
Leaves are written from fully-addressable host copies and restored with
an explicit target sharding, so a checkpoint written under one mesh
restores under any other — the property both SS-restart and failure
recovery need.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path)
        out.append((key or "leaf", leaf))
    return out


def save_tree(tree: Any, directory: str, step: int) -> str:
    """Synchronous save; returns the checkpoint path.

    Crash-safe: leaves stream into a ``.tmp`` staging directory that is
    published over ``path`` only once every leaf and the manifest have
    landed.  A failed leaf write removes the staging directory instead
    of orphaning it (``latest_step`` ignores ``.tmp`` names, but the
    garbage would accumulate), and re-saving an existing step replaces
    the old snapshot whole — ``os.replace`` cannot clobber a non-empty
    directory on its own.
    """
    path = os.path.join(directory, f"step_{step:09d}")
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    try:
        manifest = {"step": step, "leaves": []}
        for i, (key, leaf) in enumerate(_leaf_paths(tree)):
            arr = np.asarray(jax.device_get(leaf))
            fname = f"leaf_{i:05d}.npy"
            np.save(os.path.join(tmp, fname), arr)
            manifest["leaves"].append({"key": key, "file": fname, "dtype": str(arr.dtype),
                                       "shape": list(arr.shape)})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.isdir(path):
            shutil.rmtree(path)
        os.replace(tmp, path)  # atomic publish
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return path


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(name.split("_")[1])
        for name in os.listdir(directory)
        if name.startswith("step_") and not name.endswith(".tmp")
    ]
    return max(steps) if steps else None


def restore_tree(
    template: Any,
    directory: str,
    step: int,
    mesh: Optional[Mesh] = None,
    spec_tree: Any = None,
) -> Any:
    """Restore into ``template``'s structure, placing leaves on ``mesh``.

    ``template`` supplies the pytree structure (its leaf values are
    ignored); ``spec_tree`` gives the target PartitionSpecs (single spec
    or matching pytree).  Without a mesh, leaves land on the default
    device.
    """
    path = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    leaves_meta = manifest["leaves"]
    treedef = jax.tree_util.tree_structure(template)
    n = treedef.num_leaves
    if n != len(leaves_meta):
        raise ValueError(f"checkpoint has {len(leaves_meta)} leaves, template {n}")
    arrays = [np.load(os.path.join(path, m["file"])) for m in leaves_meta]
    tree = jax.tree_util.tree_unflatten(treedef, arrays)
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, tree)
    if isinstance(spec_tree, P) or spec_tree is None:
        specs = jax.tree.map(lambda _: spec_tree or P(), tree)
    else:
        specs = spec_tree
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


class CheckpointManager:
    """Periodic, optionally-async checkpointing with retention."""

    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None
        os.makedirs(directory, exist_ok=True)

    def wait(self) -> None:
        """Block until the pending async save lands; re-raise its error."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, tree: Any, step: int) -> None:
        # Snapshot to host synchronously (cheap, avoids racing mutation),
        # write to disk on a worker thread (overlaps with compute).
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        self.wait()

        def _write():
            save_tree(host_tree, self.directory, step)
            self._gc()

        def _write_async():
            try:
                _write()
            except Exception as e:  # raised again by the next wait()
                self._error = e

        if self.async_save:
            self._thread = threading.Thread(target=_write_async, daemon=True)
            self._thread.start()
        else:
            _write()

    def restore_latest(self, template: Any, mesh=None, spec_tree=None):
        self.wait()
        step = latest_step(self.directory)
        if step is None:
            return None, None
        return restore_tree(template, self.directory, step, mesh, spec_tree), step

    def _gc(self) -> None:
        steps = sorted(
            int(name.split("_")[1])
            for name in os.listdir(self.directory)
            if name.startswith("step_") and not name.endswith(".tmp")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:09d}"), ignore_errors=True)
