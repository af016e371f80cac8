"""Sharded, async, atomic checkpointing with elastic reshard-on-load.

Layout-free on purpose: leaves are stored as host numpy in logical (unsharded)
layout plus a manifest (step, tree structure fingerprint, leaf shapes/dtypes).
A restart may therefore use a different mesh or device count — the first
pjit call reshards restored arrays to the new layout (elastic scaling), and a
multi-host deployment would gather/scatter per-host shards through the same
manifest (single-process here, so save gathers to host directly).

Atomicity: write to ``step_N.tmp`` then ``os.rename`` — a crash mid-save never
corrupts the latest checkpoint. Async: saves run on a worker thread;
``wait()`` joins before restore or exit. Retention: ``keep`` newest.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np


def _structure_fingerprint(tree) -> str:
    s = str(jax.tree_util.tree_structure(tree))
    return hashlib.sha256(s.encode()).hexdigest()[:16]


_NATIVE = {"float16", "float32", "float64", "int8", "int16", "int32", "int64",
           "uint8", "uint16", "uint32", "uint64", "bool"}
_UINT_OF = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _to_storable(a: np.ndarray) -> np.ndarray:
    """bf16/f8 etc. don't survive np.save — store as same-width uints."""
    if str(a.dtype) in _NATIVE:
        return a
    return a.view(_UINT_OF[a.dtype.itemsize])


def _from_storable(a: np.ndarray, dtype_str: str) -> np.ndarray:
    if str(a.dtype) == dtype_str:
        return a
    import ml_dtypes  # registered custom dtypes (bundled with jax)

    target = np.dtype(getattr(ml_dtypes, dtype_str, dtype_str))
    if a.dtype.itemsize == target.itemsize and str(a.dtype).startswith("uint"):
        return a.view(target)
    return a.astype(target)


def leaf_name(path) -> str:
    """A key path's file stem: ``('layers', 'attn', 'wq')`` ->
    ``"layers.attn.wq"``."""
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def read_leaves(directory: str, like: Any) -> Any:
    """A tree shaped like ``like`` (arrays or ShapeDtypeStructs), each leaf
    memory-mapped from ``<directory>/<leaf_name>.npy``.

    A file holds its leaf's shape in the leaf's dtype or, for a type NumPy
    cannot store (bfloat16, the float8s), in unsigned ints of the same
    width, read back bit for bit; any other shape or dtype raises
    ValueError — nothing is cast."""
    def one(path, want):
        name = leaf_name(path)
        a = np.load(os.path.join(directory, f"{name}.npy"), mmap_mode="r")
        dt = np.dtype(want.dtype)
        if a.dtype != dt and (str(dt) in _NATIVE
                              or a.dtype != _UINT_OF[dt.itemsize]):
            raise ValueError(f"{name}: file holds {a.dtype}, want {dt}")
        if a.shape != tuple(want.shape):
            raise ValueError(f"{name}: file holds {a.shape}, want "
                             f"{tuple(want.shape)}")
        return a.view(dt)

    return jax.tree_util.tree_map_with_path(one, like)


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # ------------------------------------------------------------------
    def save(self, step: int, state: Any, *, blocking: bool = True) -> None:
        self.wait()
        # Snapshot to host synchronously (cheap vs. serialization); the disk
        # write happens on the worker thread.
        leaves, treedef = jax.tree_util.tree_flatten(state)
        host = [np.asarray(x) for x in leaves]
        fp = _structure_fingerprint(state)

        def work():
            try:
                tmp = os.path.join(self.dir, f"step_{step}.tmp")
                final = os.path.join(self.dir, f"step_{step}")
                if os.path.exists(tmp):
                    shutil.rmtree(tmp)
                os.makedirs(tmp)
                manifest = {"step": step, "fingerprint": fp,
                            "n_leaves": len(host),
                            "leaves": [{"shape": list(a.shape),
                                        "dtype": str(a.dtype)} for a in host]}
                for i, a in enumerate(host):
                    np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"),
                            _to_storable(a), allow_pickle=False)
                with open(os.path.join(tmp, "manifest.json"), "w") as f:
                    json.dump(manifest, f)
                if os.path.exists(final):
                    shutil.rmtree(final)
                os.rename(tmp, final)
                self._gc()
            except BaseException as e:      # surfaced on next wait()
                self._error = e

        if blocking:
            work()
            self._raise_if_failed()
        else:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_if_failed()

    def _raise_if_failed(self):
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    def _gc(self):
        steps = sorted(self.steps())
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"),
                          ignore_errors=True)

    # ------------------------------------------------------------------
    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    out.append(int(name.split("_", 1)[1]))
                except ValueError:
                    pass
        return sorted(out)

    def restore(self, step: int, *, like: Any, mesh=None) -> Any:
        """Restore into the structure of ``like``. ``mesh`` unused directly —
        restored leaves are host-resident; the caller's pjit in_shardings
        perform the (possibly different-mesh) resharding on first use."""
        del mesh
        self.wait()
        path = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        if manifest["fingerprint"] != _structure_fingerprint(like):
            raise ValueError("checkpoint tree structure mismatch "
                             f"(ckpt step {step})")
        leaves, treedef = jax.tree_util.tree_flatten(like)
        out = []
        for i, leaf in enumerate(leaves):
            a = np.load(os.path.join(path, f"leaf_{i:05d}.npy"))
            recorded = manifest["leaves"][i]["dtype"]
            a = _from_storable(a, recorded)
            dt = getattr(leaf, "dtype", None)
            if dt is not None and str(a.dtype) != str(dt):
                a = a.astype(dt)
            out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    def restore_latest(self, *, like: Any, mesh=None
                       ) -> tuple[Optional[Any], int]:
        steps = self.steps()
        if not steps:
            return None, 0
        s = steps[-1]
        return self.restore(s, like=like, mesh=mesh), s
