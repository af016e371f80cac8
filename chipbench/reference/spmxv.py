"""ELL sparse matrix times vector: the benchmark's own inputs and a NumPy
float64 reference."""
from __future__ import annotations

import numpy as np


def band_ell(rows: int, nnz_per_row: int, q: float,
             rng: np.random.Generator):
    """A band matrix in ELL form: row r holds columns r-w .. r+w-1
    (w = nnz/2, clipped at the edges), and each entry moves to a uniformly
    random column with probability q. Values are uniform in [0, 0.1).
    Returns (vals f32 (rows, nnz), cols i32 (rows, nnz), x f32 (rows,))."""
    w = nnz_per_row // 2
    base = np.arange(rows)[:, None] + (np.arange(nnz_per_row)[None, :] - w)
    cols = np.clip(base, 0, rows - 1).astype(np.int32)
    swap = rng.random(cols.shape) < q
    cols[swap] = rng.integers(0, rows, size=int(swap.sum()), dtype=np.int32)
    vals = (rng.random(cols.shape) * 0.1).astype(np.float32)
    x = rng.standard_normal(rows).astype(np.float32)
    return vals, cols, x


def spmv(vals, cols, x) -> np.ndarray:
    """y = A x with float64 products and sums."""
    v = np.asarray(vals, np.float64)
    xx = np.asarray(x, np.float64)
    return (v * xx[np.asarray(cols)]).sum(axis=1)


def spmv_bf16(vals, cols, x) -> np.ndarray:
    """The control: the same product with values and x rounded to
    bfloat16 (float32 accumulation), the step below float32."""
    import ml_dtypes

    v = np.asarray(vals, np.float32).astype(ml_dtypes.bfloat16)
    xx = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    prod = v.astype(np.float32) * xx.astype(np.float32)[np.asarray(cols)]
    return prod.sum(axis=1, dtype=np.float32)


def max_rel_err(got, want) -> float:
    """max |got - want| / max |want|."""
    got = np.asarray(got, np.float64).reshape(np.shape(want))
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))
