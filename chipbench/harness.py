"""One run of one cell: find the cell's files by name, hold the chip, set
up, measure, check, print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``chipbench/configs/<file>``: the configuration (``configs[].file``);
* ``chipbench/traffic/<traffic>.json``: the mix; its ``runner`` names the
  general runner in ``chipbench/runners/`` that runs it;
* ``chipbench/metrics/<metric>.py``: the reader of one per-layer metric,
  a function ``read(run)`` that returns a number or None.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from chipbench import trace as T
from chipbench.spans import Tracer
from chipbench.traffic import load_traffic

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
METRICS_DIR = BENCH_DIR / "metrics"
WORK_DIR = ".chipbench"          # under the checkout; in .gitignore


class Refused(SystemExit):
    """A run that must not print a result (exit code 2 or 3)."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"chipbench: no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str, root: Path = ROOT) -> tuple:
    """(cell, config entry, configuration file, traffic mix) of ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"chipbench: no workload {name!r}; one of "
                      f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = load_traffic(cell["traffic"], root / BENCH_DIR.name / "traffic")
    return cell, entry, config, mix


def applies(metric: dict, cell: dict, e2e_names: set) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list, or,
    without one, every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def end_to_end_of(bench: dict, cell: dict) -> list:
    return [m for m in bench["end_to_end"] if "workloads" not in m
            or cell["name"] in m["workloads"]]


def per_layer_of(bench: dict, cell: dict) -> list:
    e2e = {m["name"] for m in end_to_end_of(bench, cell)}
    return [m for m in bench["per_layer"] if applies(m, cell, e2e)]


def load_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = root / BENCH_DIR.name / METRICS_DIR.name / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def runner_class(name: str):
    """``Cell`` of ``chipbench/runners/<name>.py``."""
    return importlib.import_module(f"chipbench.runners.{name}").Cell


@dataclass
class Context:
    """What a runner gets: the seed, its files, the tracer and where it may
    write."""
    seed: int
    config: dict
    traffic: dict
    tracer: Tracer
    work_dir: Path


@dataclass
class Run:
    """What a per-layer reader gets."""
    trace: Optional[T.Trace]
    counters: dict
    config: dict
    traffic: dict
    peaks: dict


def require_devices(chips: int, *, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise Refused(f"chipbench: needs a TPU; JAX finds "
                      f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise Refused(f"chipbench: the cell needs {chips} chips; JAX finds "
                      f"{len(devs)}")
    return devs[:chips]


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def setup_compile_cache() -> str:
    import jax

    from repro.compile_cache import setup_compile_cache as program_cache

    path = program_cache()
    # cache every program, however quickly it compiles, so that a second
    # run of a cell finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(args, *, t_start: float, root: Path = ROOT,
             require_chip: bool = True, compile_cache: bool = True,
             cell_hook=None) -> dict:
    """One run; returns the result object. ``require_chip=False``,
    ``compile_cache=False`` and ``cell_hook`` (called with the runner's
    cell after set-up) are for tests on the CPU."""
    bench = load_benchmark(root)
    cell, _entry, config, mix = find_cell(bench, args.workload, root)
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"chipbench: no program under {root / 'src'}")
    devs = require_devices(cell["chips"], require_chip=require_chip)
    if compile_cache:
        setup_compile_cache()
    from chipbench.peaks import peaks_for

    peaks = peaks_for(devs[0].device_kind) if require_chip else {}
    work = root / WORK_DIR / cell["name"]
    tracer = Tracer(bool(args.trace), work / "trace")
    if args.trace and tracer.out_dir.exists():
        import shutil

        shutil.rmtree(tracer.out_dir)
    ctx = Context(seed=args.seed, config=config, traffic=mix, tracer=tracer,
                  work_dir=work)
    the_cell = runner_class(mix["runner"])(ctx)
    if cell_hook is not None:
        cell_hook(the_cell)
    setup_s = time.perf_counter() - t_start
    res = the_cell.window(args.seconds)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    counters = the_cell.counters()
    the_cell.free_program()
    t_check = time.perf_counter()
    checks = the_cell.check()
    res.setdefault("notes", {})["check_s"] = time.perf_counter() - t_check
    correct = bool(checks) and all(v <= lim for _, v, lim in checks)

    metrics: dict = {}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        tr = T.load(tracer.out_dir, devs[0].platform)
        device["busy_s"] = T.busy_s(tr)
        device["window_s"] = tr.window_s
        run = Run(tr, counters, config, mix, peaks)
        for m in per_layer_of(bench, cell):
            v = load_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": T.top_ops(tr),
                            "idle_gaps": T.idle_by_span(tr)}
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        for m in end_to_end_of(bench, cell):
            v = values.get(m["name"])
            if v is None:
                raise RuntimeError(f"the {mix['runner']} runner reported no "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["notes"] = res.get("notes", {})
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main(argv, *, t_start: float) -> int:
    args = parse(argv)
    try:
        out = run_cell(args, t_start=t_start)
    except Refused as e:
        print(e.code, file=sys.stderr, flush=True)
        return 3
    notes = out.pop("notes")            # the compared numbers stay last
    print(json.dumps({"notes": notes}, default=str), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
