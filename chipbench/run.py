"""Entry point of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds,
and prints one JSON object as the last line of standard output. It exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for, or where the checkout holds no program under ``src/``.
"""
import time

T_START = time.perf_counter()      # set-up is timed from process start

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu's own logs would go to a fixed /tmp path; a run writes only under
# its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    from chipbench.harness import main

    sys.exit(main(sys.argv[1:], t_start=T_START))
