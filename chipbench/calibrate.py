"""Readings that a cell's correctness limit is set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3

In one process (set-up once), for each seed: the kernel error the campaign
cell's check compares, from the program (the sound reading), and from the
control, the plain reference computed one precision step lower (bfloat16
inputs for a float32 SPMXV). Prints one JSON line per seed and a summary
line; the benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu's own logs would go to a fixed /tmp path; a run writes only under
# its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def campaign_readings(cell, seeds):
    from chipbench import traffic as T
    from chipbench.reference import spmxv as ref

    c = cell.cfg
    for seed in seeds:
        prog = ctrl = 0.0
        for q in c["qs"]:
            inputs = ref.band_ell(c["rows"], c["nnz_per_row"], q,
                                  T.rng_for(seed, f"check{q}"))
            want = ref.spmv(*inputs)
            ctrl = max(ctrl, ref.max_rel_err(ref.spmv_bf16(*inputs), want))
            for mode in cell.mix["modes"]:
                prog = max(prog, ref.max_rel_err(
                    cell.main_outputs(q, mode, inputs), want))
        yield seed, {"spmxv_max_rel_err": prog}, {"spmxv_max_rel_err": ctrl}


def main(argv):
    import argparse

    from chipbench import harness as H
    from chipbench.spans import Tracer

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = H.load_benchmark()
    cell, _, config, mix = H.find_cell(bench, args.workload)
    H.require_devices(cell["chips"], require_chip=True)
    H.setup_compile_cache()
    ctx = H.Context(seed=seeds[0], config=config, traffic=mix,
                    tracer=Tracer(False), work_dir=H.ROOT / H.WORK_DIR
                    / f"calibrate-{cell['name']}")
    the_cell = H.runner_class(mix["runner"])(ctx)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    rows = []
    for seed, prog, ctrl in campaign_readings(the_cell, seeds):
        rows.append((prog, ctrl))
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl}),
              flush=True)
    names = [k for k in rows[0][1]]
    print(json.dumps({n: {"program_max": max(p[n] for p, _ in rows),
                          "control_min": min(c[n] for _, c in rows),
                          "seeds": len(rows)} for n in names}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
