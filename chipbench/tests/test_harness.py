"""The harness refuses to run without a TPU or without the program, and
drives a whole tiny campaign run on the CPU by a path that only tests
take."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness as H
from chipbench.tests.tiny import make_root

ARGS = ["--seed", str(2 ** 31 + 99), "--seconds", "0.5"]


def test_refuses_without_a_tpu_and_prints_no_result(capsys):
    rc = H.main(["--workload", "spmxv.fig7-campaign", *ARGS, "--trace", "0"],
                t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_refuses_where_only_the_benchmark_files_are(tmp_path):
    shutil.copy(H.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(H.BENCH_DIR, tmp_path / H.BENCH_DIR.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "spmxv.fig7-campaign", *ARGS, "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run(root, cell, trace, **kw):
    args = H.parse(["--workload", cell, *ARGS, "--trace", str(trace)])
    return H.run_cell(args, t_start=time.perf_counter(), root=root,
                      require_chip=False, compile_cache=False, **kw)


def test_campaign_rehearsal_end_to_end(root):
    out = run(root, "tiny.campaign", 0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "campaign_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for name, c in out["checks"].items()
               if name != "spmxv_max_rel_err")
    json.dumps(out)
