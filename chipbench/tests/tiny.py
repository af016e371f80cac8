"""A checkout of its own for tests on the CPU: a BENCHMARK.json with a
tiny campaign cell, its files, and the program linked in."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.harness import BENCH_DIR, ROOT

DATA = Path(__file__).with_name("data")
CELLS = {
    "tiny.campaign": ("tiny-spmxv", "tiny-campaign"),
}


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout whose BENCHMARK.json names the tiny
    cells, with the real per-layer metrics and ``src`` linked in."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "src").symlink_to(ROOT / "src")
    (tmp / BENCH_DIR.name / "traffic").mkdir(parents=True)
    (tmp / BENCH_DIR.name / "configs").mkdir(parents=True)
    (tmp / BENCH_DIR.name / "metrics").symlink_to(BENCH_DIR / "metrics")
    bench["configs"], bench["workloads"] = [], []
    for cell, (config, traffic) in CELLS.items():
        shutil.copy(DATA / f"{traffic}.json",
                    tmp / BENCH_DIR.name / "traffic" / f"{traffic}.json")
        file = f"{BENCH_DIR.name}/configs/{config}.json"
        shutil.copy(DATA / f"{config}.json", tmp / file)
        bench["configs"].append({"name": config, "source": "test",
                                 "file": file, "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    real = {"spmxv.fig7-campaign": "tiny.campaign"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [real[w] for w in m["workloads"] if w in real]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
