"""Benchmark entry point: one cell of BENCHMARK.json, on the chips of the
machine it is started on.

    python3 benchmarks/run.py --workload tiles64.radix --seed 7 \
        --seconds 30 --trace 0

Prints, as its last stdout line, one JSON object (correct, attempted,
failed, metrics, device, [breakdown], compared).  Exits 2 with no result
when JAX finds no TPU, fewer chips than the cell asks for, or a
graphite_tpu that is not this checkout's.  benchmarks/harness/core.py
documents the run.
"""

import time

T_MAIN = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "harness"))
# The program is this checkout's (core.import_program refuses any other).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import core
    return core.main(args, T_MAIN)


if __name__ == "__main__":
    sys.exit(main())
