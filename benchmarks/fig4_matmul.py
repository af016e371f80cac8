"""Paper Fig. 4: dense matmul under fp vs L1 noise, naive ("-O0") vs
optimized ("-O3") lowering.

Expected signature (the paper's): the naive version is load/store-clogged —
it absorbs fp noise but degrades immediately under L1 noise; the optimized
version uses the hardware efficiently — a single noise pattern already costs
time (near-zero absorption in every mode).

``--pallas``: additionally run the study on the REAL tiled Pallas matmul
kernel (in the Pallas interpreter) through the campaign spine.
"""
from __future__ import annotations

import argparse

from benchmarks.common import banner, characterize, save
from repro.bench.kernels import matmul_region
from repro.core import Controller


def run_pallas(quick: bool = True) -> dict:
    """Fig 4's fp-vs-L1 axes on the real Pallas matmul kernel."""
    from repro.kernels.region import pallas_region

    banner("Fig 4 (pallas) — tiled matmul kernel, fp vs vmem noise")
    n = 128 if quick else 256
    ctl = Controller(reps=2 if quick else 3)
    region = pallas_region("matmul", backend="interpret", n=n)
    rep = characterize(ctl, region, ("fp", "vmem"))
    print(rep.summary())
    return {"region": region.name, "abs": rep.absorptions(),
            "bottleneck": rep.bottleneck.label}


def run(quick: bool = True, pallas: bool = False) -> dict:
    banner("Fig 4 — matmul -O0 vs -O3 (absorption flip under optimization)")
    n = 192 if quick else 384
    ctl = Controller(reps=3 if quick else 5, verify_payload=False)
    rows = {}
    for opt in (False, True):
        region = matmul_region(n=n, optimized=opt)
        rep = characterize(ctl, region, ("fp_add", "l1_ld"))
        rows[region.name] = {
            "abs": rep.absorptions(),
            "bottleneck": rep.bottleneck.label,
        }
        print(rep.summary())
    o0, o3 = rows["matmul_O0"]["abs"], rows["matmul_O3"]["abs"]
    flip = (o0["fp_add"] > o0["l1_ld"]) and (max(o3.values()) <= 5
                                             or o3["fp_add"] < o0["fp_add"])
    print(f"-O0 absorbs fp ({o0['fp_add']:.0f}) >> l1 ({o0['l1_ld']:.0f}); "
          f"-O3 absorbs ~nothing ({o3}) -> signature flip: {flip}")
    out = {"rows": rows, "signature_flip": bool(flip)}
    if pallas:
        out["pallas"] = run_pallas(quick)
    save("fig4_matmul", out)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--pallas", action="store_true")
    a = ap.parse_args()
    run(quick=not a.full, pallas=a.pallas)
