"""Correctness over many seeds, and the control, in one process.

    python3 benchmarks/verify.py --workload tiles64.radix --seeds 201-212
    python3 benchmarks/verify.py --workload tiles64.radix --seeds 301-303 \
        --control 8

Runs the cell's set-up and window (the same code as benchmarks/run.py)
for each seed on the chip, one after another, so the program compiles
once; then runs the plain reference for all of them in parallel host
processes and prints each seed's compared numbers.  ``--control N`` runs
the program with ``tpu/miss_chain = N`` in the reference's place: the
exact comparison has to refuse it.  The benchmark's own runs never call
this; it is how the limits in PERF.md were read.
"""

import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "harness"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _seeds(spec):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def _finish(job):
    import core
    out, sims, events, c = job
    return core.finish(out, sims, events, c)


def main(argv=None):
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=8)
    args = ap.parse_args(argv)
    import core
    c = core.cell(args.workload)
    if args.control:
        c = core.control_cell(c, args.control)
    jobs = []
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        out, sims, events = core.measure_program(c, seed, args.seconds,
                                                 False, t0)
        out["seed"] = seed
        print(f"program seed {seed}: {json.dumps(out['window'])} "
              f"metrics {json.dumps(out['metrics'])}", flush=True)
        jobs.append((out, sims, events, c))
    ctx = mp.get_context("spawn")
    with ctx.Pool(min(args.jobs, len(jobs))) as pool:
        results = pool.map(_finish, jobs)
    worst, least = {}, {}
    for r in results:
        vals = {k: v["value"] for k, v in r["compared"].items()}
        vals["reference_s"] = r["window"]["reference_s"]
        print(f"seed {r['seed']}: correct {r['correct']} failed "
              f"{r['failed']} {json.dumps(vals)}", flush=True)
        for k, v in vals.items():
            worst[k] = max(worst.get(k, v), v)
            least[k] = min(least.get(k, v), v)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "seeds": len(results),
                      "all_correct": all(r["correct"] for r in results),
                      "none_correct": not any(r["correct"] for r in results),
                      "largest": worst, "least": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
