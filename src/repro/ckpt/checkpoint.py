"""Sharded checkpointing with atomic manifests and cross-mesh restore.

Format: one directory per step containing
  manifest.json   — step, leaf paths, shapes, dtypes, save-complete marker
  data.npz        — flattened leaf arrays keyed by sanitized tree paths

Atomicity: written to ``<dir>/.tmp-<step>`` then os.rename'd — a crashed save
never shadows the previous good checkpoint (restart-safe).

Cross-mesh restore: leaves are loaded host-side and ``jax.device_put`` with
the *target* mesh's shardings, so a checkpoint taken on one mesh restores
onto a different one (the scan runtime's carry on one device <-> the
sharded runtime's carry on a site mesh).

Async: ``CheckpointManager(async_save=True)`` snapshots to host then writes
on a worker thread, overlapping I/O with the next windows.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np
from jax.tree_util import keystr, tree_flatten_with_path


def _flatten(state):
    leaves, treedef = tree_flatten_with_path(state)
    return {keystr(p): np.asarray(jax.device_get(v)) for p, v in leaves}, treedef


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True               # exists, owned by someone else
    return True


def _gc_orphan_tmp(directory: Path) -> None:
    """Remove ``.tmp-*`` staging dirs left by crashed savers.

    A save that dies between ``tmp.mkdir()`` and the ``os.rename`` leaks
    its staging directory forever (the atomic-rename design never revisits
    it).  Each tmp name embeds the writer's pid, so on the next save we can
    tell an orphan from a concurrent writer: dirs whose pid is dead (or
    whose legacy name carries no pid) are torn down, our own and live
    writers' dirs are left alone.
    """
    for d in directory.glob(".tmp-*"):
        if not d.is_dir():
            continue
        parts = d.name.split("-")
        try:
            pid = int(parts[2])
        except (IndexError, ValueError):
            shutil.rmtree(d, ignore_errors=True)   # pre-pid legacy name
            continue
        if pid == os.getpid() or _pid_alive(pid):
            continue
        shutil.rmtree(d, ignore_errors=True)


def save(state, step: int, directory: str | Path) -> Path:
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    _gc_orphan_tmp(directory)
    tmp = directory / f".tmp-{step}-{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    flat, _ = _flatten(state)
    np.savez(tmp / "data.npz", **{k: v for k, v in flat.items()})
    manifest = {
        "step": int(step),
        "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                   for k, v in flat.items()},
        "time": time.time(),
        "complete": True,
    }
    (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
    final = directory / f"step_{step:08d}"
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory: str | Path) -> Optional[int]:
    directory = Path(directory)
    if not directory.exists():
        return None
    steps = []
    for d in directory.iterdir():
        if d.name.startswith("step_") and (d / "manifest.json").exists():
            try:
                m = json.loads((d / "manifest.json").read_text())
                if m.get("complete"):
                    steps.append(int(m["step"]))
            except (json.JSONDecodeError, KeyError, ValueError):
                continue          # torn manifest => treat as absent
    return max(steps) if steps else None


def restore(directory: str | Path, step: int, abstract_state,
            shardings=None):
    """Load a checkpoint into the structure of ``abstract_state``; if
    ``shardings`` (matching pytree of jax.sharding.Sharding) is given, leaves
    are placed sharded — onto whatever mesh those shardings reference."""
    d = Path(directory) / f"step_{step:08d}"
    data = np.load(d / "data.npz")
    leaves, treedef = tree_flatten_with_path(abstract_state)
    sh_leaves = jax.tree.leaves(shardings) if shardings is not None else \
        [None] * len(leaves)
    out = []
    for (path, ab), sh in zip(leaves, sh_leaves):
        key = keystr(path)
        arr = data[key]
        if tuple(arr.shape) != tuple(ab.shape):
            raise ValueError(f"shape mismatch restoring {key}: "
                             f"{arr.shape} vs {ab.shape}")
        arr = arr.astype(ab.dtype)
        out.append(jax.device_put(arr, sh) if sh is not None
                   else jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


class CheckpointManager:
    """Keeps the last ``keep`` checkpoints; optional async writer thread."""

    def __init__(self, directory: str | Path, keep: int = 3,
                 async_save: bool = False):
        self.directory = Path(directory)
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None

    def save(self, state, step: int):
        if self.async_save:
            flat = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
            self.wait()
            self._thread = threading.Thread(
                target=self._save_and_gc, args=(flat, step), daemon=True)
            self._thread.start()
        else:
            self._save_and_gc(state, step)

    def _save_and_gc(self, state, step):
        save(state, step, self.directory)
        kept = sorted(d for d in self.directory.iterdir()
                      if d.name.startswith("step_"))
        for d in kept[:-self.keep]:
            shutil.rmtree(d, ignore_errors=True)

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def latest(self) -> Optional[int]:
        self.wait()
        return latest_step(self.directory)

    def restore(self, abstract_state, shardings=None, step: Optional[int] = None):
        step = step if step is not None else self.latest()
        if step is None:
            return None, None
        return restore(self.directory, step, abstract_state, shardings), step
