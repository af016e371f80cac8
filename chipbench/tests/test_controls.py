"""Each cell's control, the plain reference one precision step below the
configuration's, fails the cell's limit where the program passes it (the
same comparison as on the chip, at a size a test run holds)."""
import time

import pytest

from chipbench import harness as H
from chipbench.calibrate import campaign_readings
from chipbench.tests.tiny import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run(root, cell, seed):
    got = {}
    out = H.run_cell(H.parse(["--workload", cell, "--seed", str(seed),
                              "--seconds", "1.5", "--trace", "0"]),
                     t_start=time.perf_counter(), root=root,
                     require_chip=False, compile_cache=False,
                     cell_hook=lambda c: got.setdefault("cell", c))
    return out, got["cell"]


def test_the_bfloat16_control_fails_the_spmxv_limit(root):
    out, cell = run(root, "tiny.campaign", 11)
    limit = cell.mix["correct"]["spmxv_max_rel_err"]
    for _seed, program, control in campaign_readings(cell, [3, 4, 5]):
        assert program["spmxv_max_rel_err"] < limit \
            < control["spmxv_max_rel_err"]
