"""Paper Fig. 7/8 + Table 4 lead-in: the SPMXV case study.

Sweep the swap probability q on a small (cache-resident at q=0) and a large
(bandwidth-bound at q=0) matrix; measure GFLOPS and FP/L1 absorption. The
paper's finding: on the large matrix, performance only decreases with q while
absorption first DROPS (bandwidth regime tightening) then RISES again
(latency regime: stalls reappear as dependency slack) — a regime transition
invisible to plain performance numbers.

``--pallas``: additionally run the q-sweep on the REAL ELL SPMV Pallas
kernel (in the Pallas interpreter) through the campaign spine.
"""
from __future__ import annotations

import argparse

from benchmarks.common import banner, characterize, save
from repro.bench.kernels import spmxv_region
from repro.core import Controller, measure


def run_pallas(quick: bool = True) -> dict:
    """The q-study on the real Pallas ELL SPMV kernel."""
    from repro.kernels.region import pallas_region

    banner("Fig 7 (pallas) — ELL SPMV kernel: performance vs absorption")
    qs = (0.0, 0.5, 1.0) if quick else (0.0, 0.25, 0.5, 0.75, 1.0)
    n = 512 if quick else 2048
    nnz = 16
    ctl = Controller(reps=2 if quick else 3)
    rows = []
    for q in qs:
        region = pallas_region("spmxv", backend="interpret", n=n,
                               nnz_per_row=nnz, q=q)
        t0 = measure(region.build("", 0), region.args_for("", 0),
                     reps=2 if quick else 3)
        gflops = 2.0 * n * nnz / t0 / 1e9
        rep = characterize(ctl, region, ("fp", "vmem"))
        rows.append({"q": q, "region": region.name, "gflops": gflops,
                     "abs_fp": rep.results["fp"].fit.k1,
                     "abs_vmem": rep.results["vmem"].fit.k1,
                     "label": rep.bottleneck.label})
        r = rows[-1]
        print(f"  pallas q={q:4.2f}  {gflops:6.3f} GFLOP/s  "
              f"Abs_FP={r['abs_fp']:6.1f} Abs_VMEM={r['abs_vmem']:6.1f} "
              f"-> {r['label']}")
    return {"rows": rows}


def run(quick: bool = True, pallas: bool = False) -> dict:
    banner("Fig 7/8 — SPMXV: performance vs absorption across q")
    qs = (0.0, 0.25, 0.5, 1.0) if quick else (0.0, 0.1, 0.25, 0.5, 0.75, 1.0)
    sizes = {"small": 1 << 17, "large": 1 << 21}
    nnz = 16
    ctl = Controller(reps=3 if quick else 5, verify_payload=False)
    out: dict = {}
    for label, n in sizes.items():
        rows = []
        for q in qs:
            region = spmxv_region(n=n, nnz_per_row=nnz, q=q,
                                  name=f"spmxv_{label}_q{q}")
            t0 = measure(region.build("", 0), region.args_for("", 0),
                         reps=3 if quick else 5)
            gflops = 2.0 * n * nnz / t0 / 1e9
            rep = characterize(ctl, region, ("fp_add", "l1_ld"))
            rows.append({"q": q, "gflops": gflops,
                         "abs_fp": rep.results["fp_add"].fit.k1,
                         "abs_l1": rep.results["l1_ld"].fit.k1,
                         "label": rep.bottleneck.label})
            r = rows[-1]
            print(f"  {label:5s} q={q:4.2f}  {gflops:6.2f} GFLOP/s  "
                  f"Abs_FP={r['abs_fp']:6.1f} Abs_L1={r['abs_l1']:6.1f} "
                  f"-> {r['label']}")
        out[label] = rows

    lg = out["large"]
    perf_monotonic = all(lg[i]["gflops"] >= lg[i + 1]["gflops"] - 0.15
                         for i in range(len(lg) - 1))
    fp_abs = [r["abs_fp"] for r in lg]
    non_monotonic = any(fp_abs[i] > min(fp_abs[:i] or [1e9])
                        for i in range(1, len(fp_abs)))
    print(f"  large: performance monotonically falls: {perf_monotonic}; "
          f"absorption non-monotonic (regime transition): {non_monotonic}")
    out["findings"] = {"perf_monotonic": perf_monotonic,
                       "absorption_non_monotonic": non_monotonic}
    if pallas:
        out["pallas"] = run_pallas(quick)
    save("fig7_spmxv", out)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--pallas", action="store_true")
    a = ap.parse_args()
    run(quick=not a.full, pallas=a.pallas)
