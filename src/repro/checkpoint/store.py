"""Atomic, async, keep-last-N checkpointing without external dependencies.

Layout:   <root>/step_<N>/manifest.json + leaf_<i>.npy
Atomicity: written into step_<N>.tmp, fsync'd, then os.rename — a reader
never observes a partial checkpoint, and a crash mid-save leaves the previous
checkpoint intact.
Async mode hands the host-side write to a worker thread so the train loop
only blocks for the device->host copy.
"""
from __future__ import annotations

import concurrent.futures
import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any

import jax
import ml_dtypes
import numpy as np


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def _to_host(v) -> np.ndarray:
    """Device->host with bf16 handled (numpy exposes it as void-2)."""
    a = np.asarray(v)
    if a.dtype == np.dtype("V2"):
        a = a.view(ml_dtypes.bfloat16)
    return a


def _from_host(a: np.ndarray) -> np.ndarray:
    if a.dtype == np.dtype("V2"):
        a = a.view(ml_dtypes.bfloat16)
    return a


class CheckpointStore:
    def __init__(self, root: str | Path, *, keep: int = 3, use_async: bool = False):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.use_async = use_async
        self._pool = (
            concurrent.futures.ThreadPoolExecutor(max_workers=1) if use_async else None
        )
        self._pending: concurrent.futures.Future | None = None
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def save(self, step: int, tree: Any) -> None:
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        # Device->host copy happens synchronously (consistent snapshot) ...
        host_leaves = [(p, _to_host(v)) for p, v in leaves]
        if self.use_async:
            self.wait()
            self._pending = self._pool.submit(self._write, step, host_leaves)
        else:
            self._write(step, host_leaves)

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    def _write(self, step: int, host_leaves) -> None:
        with self._lock:
            final = self.root / f"step_{step:010d}"
            tmp = self.root / f"step_{step:010d}.tmp"
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            manifest = {"step": step, "leaves": []}
            for i, (path, arr) in enumerate(host_leaves):
                fn = f"leaf_{i:05d}.npy"
                np.save(tmp / fn, arr)
                manifest["leaves"].append(
                    {"path": _path_str(path), "file": fn,
                     "shape": list(arr.shape), "dtype": str(arr.dtype)}
                )
            (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
            # fsync the directory entry for crash consistency
            fd = os.open(tmp, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)
            self._prune()

    def _prune(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep > 0 else []:
            shutil.rmtree(self.root / f"step_{s:010d}", ignore_errors=True)

    # ------------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for d in self.root.glob("step_*"):
            if d.suffix == ".tmp" or not (d / "manifest.json").exists():
                continue
            out.append(int(d.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def read_arrays(self, step: int | None = None) -> dict[str, np.ndarray]:
        """Read one checkpoint as {keystr path: host array} without a `like`
        tree — the serving loader's entry point (the server does not know the
        trainer's pytree structure, only the manifest)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.root}")
        d = self.root / f"step_{step:010d}"
        manifest = json.loads((d / "manifest.json").read_text())
        out = {
            meta["path"]: _from_host(np.load(d / meta["file"]))
            for meta in manifest["leaves"]
        }
        # insertion order == manifest order; paths are unique by construction
        assert len(out) == len(manifest["leaves"])
        return out

    def restore(self, like: Any, step: int | None = None) -> Any:
        """Restore into the structure of `like` (shapes validated)."""
        raw = self.read_arrays(step)
        leaves, treedef = jax.tree_util.tree_flatten(like)
        assert len(leaves) == len(raw), (len(leaves), len(raw))
        out = []
        for leaf, (path, arr) in zip(leaves, raw.items()):
            expected = tuple(getattr(leaf, "shape", arr.shape))
            assert tuple(arr.shape) == expected, (path, arr.shape, expected)
            out.append(jax.device_put(arr))
        return jax.tree_util.tree_unflatten(treedef, out)
