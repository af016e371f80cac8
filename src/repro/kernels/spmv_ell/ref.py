"""Oracles for ELL SPMV and the ELL matrix generators used by the SPMXV
case study (band matrix with swap probability q, paper §6).

``spmv_ell_ref`` is a jnp oracle of the kernel's output. The nacc oracles
``fp_noise_ell_ref`` and ``vmem_noise_ell_ref`` are NumPy host oracles: they
take ``vals`` off the device once and add in float32, block by block and
pattern by pattern, so a payload check dispatches no device program per
block or pattern."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def spmv_ell_ref(vals, cols, x):
    """y[r] = sum_l vals[r,l] * x[cols[r,l]] (padded entries have vals=0)."""
    g = jnp.take(x, cols, axis=0)
    return jnp.sum(vals.astype(jnp.float32) * g.astype(jnp.float32),
                   axis=1).astype(x.dtype)


def fp_noise_ell_ref(vals, k_noise: int, br: int = 128) -> np.ndarray:
    """Exact nacc oracle for spmv_ell mode='fp'.

    The kernel has no noise operand; block i's addend is its first 8 rows'
    first column broadcast across lanes (noise_slots._fp_c with a src_ref),
    so nacc = k * sum_i broadcast(vals[i*br : i*br+8, 0]).
    """
    v = np.asarray(vals, np.float32)
    R = v.shape[0]
    br = min(br, R)
    c = np.zeros((8, 1), np.float32)
    for i in range(R // br):
        c += v[i * br:i * br + 8, 0:1]
    return np.float32(k_noise) * np.broadcast_to(c, (8, 128))


def vmem_noise_ell_ref(vals, k_noise: int, br: int = 128) -> np.ndarray:
    """Exact nacc oracle for spmv_ell mode='vmem': block i re-reads its own
    (8, min(L,128)) row groups at rotating offsets (step index = i)."""
    v = np.asarray(vals, np.float32)
    R, L = v.shape
    br = min(br, R)
    w = min(L, 128)
    acc = np.zeros((8, 128), np.float32)
    for i in range(R // br):
        blk = v[i * br:(i + 1) * br, 0:w]
        for j in range(k_noise):
            off = (i * 7 + j * 13) % max(br - 8, 1)
            acc[:, 0:w] += blk[off:off + 8]
    return acc


def make_band_ell(n: int, nnz_per_row: int, q: float, seed: int = 0,
                  dtype=np.float32):
    """Banded sparse matrix in ELL with the paper's swap-probability q.

    At q=0 the nonzeros of row r sit at columns r-w..r+w (stride-1 vector
    access, prefetch friendly). Each nonzero is swapped with probability q to
    a uniformly random column — monotonically increasing the irregularity of
    the x gather, exactly the paper's knob for driving SPMXV from
    bandwidth-bound to latency-bound.
    """
    rng = np.random.RandomState(seed)
    w = nnz_per_row // 2
    base = np.arange(n)[:, None] + (np.arange(nnz_per_row)[None, :] - w)
    cols = np.clip(base, 0, n - 1).astype(np.int32)
    swap = rng.random_sample(cols.shape) < q
    cols[swap] = rng.randint(0, n, size=int(swap.sum()), dtype=np.int32)
    vals = rng.random_sample(cols.shape).astype(dtype) * 0.1
    return jnp.asarray(vals), jnp.asarray(cols)
