"""A checkout of its own for the serve cells' tests on the CPU: a
BENCHMARK.json with a tiny decode-campaign cell, and, in the program, a
DeepSeek-Coder-shaped configuration at tiny widths under the name the
cell's configuration file gives (GQA 7:1, RoPE theta 1e5 with linear
scaling x4, RMSNorm eps 1e-6, untied head, 2 layers, float32)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.harness import BENCH_DIR, ROOT

DATA = Path(__file__).with_name("data")
CELL, CONFIG, TRAFFIC = "tiny.decode-campaign", "tiny-dsc", \
    "tiny-decode-campaign"


def tiny_config(name: str = CONFIG):
    """The program's ModelConfig of ``data/tiny-dsc.json``."""
    from repro.configs import ModelConfig

    c = json.loads((DATA / f"{CONFIG}.json").read_text())
    return ModelConfig(
        name=name, family="dense", n_layers=c["n_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        vocab_size=c["vocab_size"], norm_eps=c["norm_eps"],
        rope_theta=c["rope_theta"],
        rope_scaling=c["rope_scaling"]["factor"],
        param_dtype=c["dtype"], compute_dtype=c["dtype"])


def register_tiny_config(monkeypatch) -> None:
    """Let the program's ``get_config`` find the tiny configuration."""
    import repro.configs as configs

    get_config = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name: tiny_config()
                        if name == CONFIG else get_config(name))


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout whose BENCHMARK.json names the tiny
    decode-campaign cell, with the real per-layer metrics and ``src``
    linked in."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "src").symlink_to(ROOT / "src")
    for sub in ("traffic", "configs"):
        (tmp / BENCH_DIR.name / sub).mkdir(parents=True)
    (tmp / BENCH_DIR.name / "metrics").symlink_to(BENCH_DIR / "metrics")
    shutil.copy(DATA / f"{TRAFFIC}.json",
                tmp / BENCH_DIR.name / "traffic" / f"{TRAFFIC}.json")
    file = f"{BENCH_DIR.name}/configs/{CONFIG}.json"
    shutil.copy(DATA / f"{CONFIG}.json", tmp / file)
    bench["configs"] = [{"name": CONFIG, "source": "test", "file": file,
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL for w in m["workloads"]
                              if w == "dsc33b.decode-campaign"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
