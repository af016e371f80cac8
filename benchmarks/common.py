"""Shared harness bits for the per-paper-table benchmarks."""
from __future__ import annotations

import json
import os
import time
from typing import Any

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "experiments/bench")

# When set (benchmarks.run sets it by default), every characterization sweep
# becomes a resumable campaign: measured points persist under this directory
# and a re-run only measures what the store is missing.
CAMPAIGN_DIR_VAR = "REPRO_CAMPAIGN_DIR"


def characterize(ctl, region, modes) -> Any:
    """``Controller.characterize`` through the fleet executor's store-backed
    spine when a store directory is configured (the same code path fleet
    finalize runs), plain (non-persistent) otherwise."""
    campaign_dir = os.environ.get(CAMPAIGN_DIR_VAR, "")
    if not campaign_dir:
        return ctl.characterize(region, modes=modes)
    from repro.fleet.executor import characterize_region

    return characterize_region(
        region, modes, controller=ctl,
        store=os.path.join(campaign_dir, f"{region.name}.jsonl"))


def run_decan_stored(target, *, reps: int, inner: int = 1) -> Any:
    """``run_decan`` through the campaign store when a store directory is
    configured — DECAN variant timings land in the SAME per-region file as
    the noise sweeps, and a re-run replays them instead of remeasuring."""
    from repro.core import CampaignStats, CampaignStore
    from repro.core.decan import run_decan

    campaign_dir = os.environ.get(CAMPAIGN_DIR_VAR, "")
    if not campaign_dir:
        return run_decan(target, reps=reps, inner=inner)
    store = CampaignStore(os.path.join(campaign_dir, f"{target.name}.jsonl"))
    stats = CampaignStats()
    try:
        res = run_decan(target, reps=reps, inner=inner, store=store,
                        stats=stats)
    finally:
        store.close()
    if stats.cached:
        print(f"  [{target.name}: {stats.cached} DECAN variant(s) from "
              f"store, {stats.measured} measured]")
    return res


def save(name: str, payload: Any) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


def banner(title: str) -> None:
    print(f"\n=== {title} {'=' * max(0, 66 - len(title))}")


class timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
