"""The yardstick's byte counts against hand-computed values."""
from chipbench.counts import spmxv_ell_bytes


def test_spmxv_bytes_at_2_18_rows():
    # vals 16.78 MB + cols 16.78 MB + x 1.05 MB + y 1.05 MB
    assert spmxv_ell_bytes(1 << 18, 16) == 35_651_584
    # and at the campaign cell's 2^15 rows
    assert spmxv_ell_bytes(1 << 15, 16) == 4_456_448
