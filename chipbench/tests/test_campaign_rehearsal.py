"""A whole traced run of the campaign cell's runner on the CPU, with the
kernel in the Pallas interpreter (a path the harness takes only in tests)."""
import time

from chipbench import harness as H
from chipbench.tests.tiny import make_root


def test_campaign_rehearsal_traced(tmp_path):
    root = make_root(tmp_path)
    args = H.parse(["--workload", "tiny.campaign", "--seed", "4242",
                    "--seconds", "1", "--trace", "1"])
    out = H.run_cell(args, t_start=time.perf_counter(), root=root,
                     require_chip=False, compile_cache=False)
    assert out["correct"] is True
    assert out["attempted"] == 1 and out["failed"] == 0
    # readers that need a chip's peaks find nothing on the CPU
    assert set(out["metrics"]) == {"campaign.compile_s", "campaign.points",
                                   "device_idle.campaign"}
    assert out["metrics"]["campaign.points"]["value"] > 0
    assert out["device"]["busy_s"] > 0
    spans = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert spans <= {"campaign.run_fleet", "bench.kernel_k0", "none"}
    err = out["checks"]["spmxv_max_rel_err"]
    assert err["value"] < err["limit"]
