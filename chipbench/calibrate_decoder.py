"""Readings that a serve cell's logits limit is set from, on the chip.

    python3 chipbench/calibrate_decoder.py --workload <cell> --seeds 1,2,3

Sets the cell up once, then for each seed (weights and prompts): the error
of the decode tick's logits against the float32 reference, from the
program (the sound reading) and from the control, the same tick where the
program's weight load rounds every weight through float8_e4m3fn before its
engine sees it (the reference reads the weight files as drawn). Prints one
JSON line per seed and a summary line; the benchmark's own runs never run
the control.
"""
import time

T_START = time.perf_counter()

import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu's own logs would go to a fixed /tmp path; a run writes only under
# its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONTROL = "float8_e4m3fn"
NAME = "tick_logits_max_rel_err"


def rounded_load(load, dtype: str = CONTROL):
    """``load`` (the program's ``repro.serve.load.load_weights``) with
    every leaf it returns rounded through ``dtype`` and back, one array at a
    time: each leaves the device once its rounded copy exists."""
    import jax

    def one(x):
        y = x.astype(dtype).astype(x.dtype)
        x.delete()
        return y

    def rounded(directory, like):
        return jax.tree.map(one, load(directory, like))

    return rounded


@contextlib.contextmanager
def control_weights(dtype: str = CONTROL):
    """Within it, the program loads its weights rounded through ``dtype``."""
    from repro.serve import load as serve_load

    load = serve_load.load_weights
    serve_load.load_weights = rounded_load(load, dtype)
    try:
        yield
    finally:
        serve_load.load_weights = load


def tick_readings(cell, seeds):
    """(seed, sound reading, control reading) for each seed, on ``cell``
    (a serve-campaign runner whose set-up has run)."""
    for seed in seeds:
        cell.ctx.seed = seed
        sound = cell.tick_error()
        with control_weights():
            control = cell.tick_error()
        cell.free_program()
        yield seed, {NAME: sound}, {NAME: control}
    cell.drop_weights()


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
    for seed, prog, ctrl in tick_readings(the_cell, seeds):
        rows.append((prog, ctrl))
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "t": round(time.perf_counter() - T_START, 1)}),
              flush=True)
    print(json.dumps({NAME: {"program_max": max(p[NAME] for p, _ in rows),
                             "control_min": min(c[NAME] for _, c in rows),
                             "seeds": len(rows)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
