"""Bytes that a kernel must move, from shapes.

These are the yardstick's numerators: a roofline share divides them by a
device time from the trace and a peak from ``peaks.json``. Nothing here
reads the program; every count comes from the sizes in a configuration
file.
"""
from __future__ import annotations


def spmxv_ell_bytes(rows: int, nnz_per_row: int) -> int:
    """Compulsory HBM traffic of one float32 ELL SPMXV y = A x with a square
    A and int32 column indices: every value and index once, x and y once."""
    return rows * nnz_per_row * (4 + 4) + 2 * rows * 4

