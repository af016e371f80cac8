"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig5,fig7]

Writes per-table JSON to experiments/bench/ and prints the summary tables.

Characterization sweeps run as resumable campaigns by default: every measured
(region, mode, k, t) point lands in a JSONL store under --campaign-dir, and a
re-run (after a crash, a ctrl-C, or to add modes) only measures what is
missing. ``--no-campaign`` restores the old measure-everything-every-time
behaviour; delete the store directory to force fresh numbers.

``--emit-fleet-plan PATH`` turns the harness into a plan builder: instead of
measuring, it writes a ``repro.fleet`` SweepPlan spanning the fig4/fig7
Pallas size/q FAMILIES (the whole grid the ``--pallas`` studies sample), to
be fanned out across subprocess shards or hosts:

    PYTHONPATH=src python -m benchmarks.run --emit-fleet-plan plan.json
    PYTHONPATH=src python -m repro.fleet run --plan plan.json
"""
from __future__ import annotations

import argparse
import os
import time


def build_fleet_plan(quick: bool, *, store: str, shards: int = 2,
                     out: str = "fleet_plan.json") -> str:
    """The fig4/fig7 Pallas grids as one declarative SweepPlan: the matmul
    size family and the spmxv (size × q) family share one store, one fleet,
    one merged classification."""
    from repro.fleet.plan import SweepPlan, TargetSpec

    if quick:
        m_sizes, s_sizes, qs = [128, 256], [256, 512], [0.0, 1.0]
    else:
        m_sizes, s_sizes, qs = [256, 512], [512, 2048], [0.0, 0.5, 1.0]
    plan = SweepPlan(
        name=f"bench_pallas_{'quick' if quick else 'full'}",
        store=store,
        targets=[
            TargetSpec("pallas", ("fp", "vmem"),
                       {"kernel": "matmul", "sizes": m_sizes}),
            TargetSpec("pallas", ("fp", "vmem"),
                       {"kernel": "spmxv", "sizes": s_sizes, "qs": qs,
                        "nnz_per_row": 16}),
        ],
        reps=2 if quick else 3, shards=shards, backend="interpret")
    plan.save(out)
    grid = plan.grid()
    print(f"wrote fleet plan {plan.name!r} [{plan.digest()}] -> {out}")
    print(f"  {len(grid)} (region, mode) pair(s) over {shards} shard(s); "
          f"store: {store}")
    print(f"run it:   PYTHONPATH=src python -m repro.fleet run --plan {out}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger sizes / more reps (slower, steadier)")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few reps (the default; the explicit "
                         "flag exists for scripts and CI smoke jobs)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig5,table3")
    ap.add_argument("--campaign-dir", default="experiments/campaigns/bench",
                    help="JSONL store directory for resumable sweeps")
    ap.add_argument("--no-campaign", action="store_true",
                    help="measure every point afresh (no persistence)")
    ap.add_argument("--pallas", action="store_true",
                    help="also run fig4/fig7 on the real Pallas kernels "
                         "(in the Pallas interpreter)")
    ap.add_argument("--emit-fleet-plan", default=None, metavar="PATH",
                    help="write a repro.fleet SweepPlan covering the "
                         "fig4/fig7 Pallas size/q families to PATH and "
                         "exit (run it with python -m repro.fleet run)")
    ap.add_argument("--fleet-shards", type=int, default=2,
                    help="shard count baked into --emit-fleet-plan")
    args = ap.parse_args()
    if args.quick and args.full:
        ap.error("--quick and --full are mutually exclusive")
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.emit_fleet_plan:
        build_fleet_plan(
            not args.full, out=args.emit_fleet_plan,
            shards=args.fleet_shards,
            store=os.path.join(args.campaign_dir,
                               "full" if args.full else "quick",
                               "bench_pallas_fleet.jsonl"))
        return

    from benchmarks.common import CAMPAIGN_DIR_VAR
    if args.no_campaign:
        os.environ.pop(CAMPAIGN_DIR_VAR, None)
    else:
        # quick/full use different region sizes: separate stores so a --full
        # run never replays quick-mode timings (region names don't encode n)
        os.environ[CAMPAIGN_DIR_VAR] = os.path.join(
            args.campaign_dir, "full" if args.full else "quick")

    from benchmarks import (fig4_matmul, fig5_hwchar, fig6_overlap,
                            fig7_spmxv, table1_systems, table3_decan,
                            table4_memsys)

    suite = {
        "fig4": lambda quick: fig4_matmul.run(quick=quick,
                                              pallas=args.pallas),
        "fig5": fig5_hwchar.run,
        "table1": table1_systems.run,
        "table3": table3_decan.run,
        "fig6": fig6_overlap.run,
        "fig7": lambda quick: fig7_spmxv.run(quick=quick,
                                             pallas=args.pallas),
        "table4": table4_memsys.run,
    }
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    t_all = time.time()
    results = {}
    for name, fn in suite.items():
        if only and name not in only:
            continue
        t0 = time.time()
        results[name] = fn(quick=not args.full)
        print(f"[{name} done in {time.time()-t0:.1f}s]")
    print(f"\nall benchmarks done in {time.time()-t_all:.1f}s "
          f"-> experiments/bench/*.json")


if __name__ == "__main__":
    main()
