"""The weights a serve cell's program and its reference share.

The benchmark draws them from the run's seed, in NumPy, at the
configuration's shapes and in its dtype, and writes one ``.npy`` a
parameter into a directory of the run's work dir. The program reads that
directory through its serve kind's ``weights`` param; the reference reads
the same files (``by_role``). So what the program does to the weights it
loads, a cast through a narrower type for one, reaches the tick's logits
and not the reference's.

A file is named by the parameter's key path in the program's tree of a
dense Llama-block model, layers stacked on a leading axis
(``layers.attn.wq``, (layers, d, heads, head_dim)). A dtype NumPy cannot
store (bfloat16) is written as unsigned ints of its width, bit for bit.

Values are uniform with the standard deviation the program's own
initialiser uses (1/sqrt(fan-in); the embedding 0.02), and the norm
scales uniform in [0.75, 1.25], not 1, so that a norm the reference
skipped would show. Uniform, not normal: NumPy draws it about five times
faster, and 8 of DeepSeek-Coder-33B's layers hold 4.7e9 values. Each chunk
of ``CHUNK`` values has a generator of its own, seeded by (seed, file,
chunk), so the values do not depend on how many threads draw them.
"""
from __future__ import annotations

import os
import shutil
from collections.abc import Sequence
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

CHUNK = 1 << 24
NORM_HALF_WIDTH = 0.25

# the reference's roles of one layer -> the program's names
LAYER_FILES = {"attn_norm": "layers.ln1.scale", "wq": "layers.attn.wq",
               "wk": "layers.attn.wk", "wv": "layers.attn.wv",
               "wo": "layers.attn.wo", "mlp_norm": "layers.ln2.scale",
               "w_gate": "layers.mlp.w_gate", "w_up": "layers.mlp.w_up",
               "w_down": "layers.mlp.w_down"}


def dtype_of(c: dict) -> np.dtype:
    import ml_dtypes

    return np.dtype(getattr(ml_dtypes, c["dtype"], c["dtype"]))


def files(c: dict) -> dict:
    """name -> (shape, standard deviation; None for a norm scale)."""
    L, d, f, v = c["n_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return {"embed.table": ((v, d), 0.02),
            "embed.head": ((d, v), d ** -0.5),
            "final_norm.scale": ((d,), None),
            "layers.ln1.scale": ((L, d), None),
            "layers.attn.wq": ((L, d, h, hd), d ** -0.5),
            "layers.attn.wk": ((L, d, kv, hd), d ** -0.5),
            "layers.attn.wv": ((L, d, kv, hd), d ** -0.5),
            "layers.attn.wo": ((L, h, hd, d), (h * hd) ** -0.5),
            "layers.ln2.scale": ((L, d), None),
            "layers.mlp.w_gate": ((L, d, f), d ** -0.5),
            "layers.mlp.w_up": ((L, d, f), d ** -0.5),
            "layers.mlp.w_down": ((L, f, d), f ** -0.5)}


def _stored(dt: np.dtype) -> np.dtype:
    return dt if dt.kind in "fiub" else np.dtype(f"u{dt.itemsize}")


def draw(c: dict, seed: int, directory: Path, *,
         threads: int | None = None) -> Path:
    """Write configuration ``c``'s weights, drawn from ``seed``, into
    ``directory`` (made anew; a partial draw never stands under its
    name)."""
    dt = dtype_of(c)
    tmp = directory.with_name(directory.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    outs, tasks = {}, []
    for i, (name, (shape, std)) in enumerate(files(c).items()):
        out = np.lib.format.open_memmap(tmp / f"{name}.npy", mode="w+",
                                        dtype=_stored(dt), shape=shape)
        outs[name] = out
        flat = out.reshape(-1)
        tasks += [(i, j, flat, std) for j in range(-(-flat.size // CHUNK))]

    def fill(task):
        i, j, flat, std = task
        part = flat[j * CHUNK:(j + 1) * CHUNK]
        x = np.random.default_rng([seed, i, j]).random(part.size, np.float32)
        x -= np.float32(0.5)
        if std is None:
            x *= np.float32(2 * NORM_HALF_WIDTH)
            x += np.float32(1)
        else:
            x *= np.float32(2 * 3 ** 0.5 * std)
        part[...] = x.astype(dt).view(part.dtype)

    with ThreadPoolExecutor(threads or min(16, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, tasks))
    for out in outs.values():
        out.flush()
    del outs, tasks
    shutil.rmtree(directory, ignore_errors=True)
    tmp.rename(directory)
    return directory


def load(directory: Path, name: str, c: dict) -> np.ndarray:
    """One file, memory-mapped, in the configuration's dtype."""
    return np.load(Path(directory) / f"{name}.npy",
                   mmap_mode="r").view(dtype_of(c))


class _Layers(Sequence):
    """The stacked layer files as a sequence of per-layer role mappings,
    each layer read only when it is asked for."""

    def __init__(self, stacked: dict):
        self.stacked = stacked
        self.n = stacked["attn_norm"].shape[0]

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if not 0 <= i < self.n:
            raise IndexError(i)
        return {role: a[i] for role, a in self.stacked.items()}


def by_role(directory: Path, c: dict) -> dict:
    """The weights in ``directory`` as ``chipbench.reference.decoder`` takes
    them."""
    return {"embed": load(directory, "embed.table", c),
            "head": load(directory, "embed.head", c),
            "final_norm": load(directory, "final_norm.scale", c),
            "layers": _Layers({role: load(directory, name, c)
                               for role, name in LAYER_FILES.items()})}
