"""Record chip_smoke.py's fixture on the CPU: the digests (completion,
round counters, progress sums, per-counter sums) of its radix headline
and probe workloads, which the chip run must then equal bit for bit.

    JAX_PLATFORMS=cpu python tools/record_smoke_fixture.py [KEY ...]

Keys default to every HEADLINE and PROBE entry; recording a subset
keeps the other entries of the existing file.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402


def main(argv) -> int:
    workloads = {**chip_smoke.HEADLINE, **chip_smoke.PROBE}
    keys = argv or list(workloads)
    fixture = {}
    if os.path.exists(chip_smoke.FIXTURE):
        with open(chip_smoke.FIXTURE) as f:
            fixture = json.load(f)
    for key in keys:
        w = workloads[key]
        sim, summary, host_s = chip_smoke.run_workload(
            chip_smoke.workload_params(w["tiles"], chip_smoke.probe_sets(w)),
            chip_smoke.radix_trace(w), w["steps"])
        fixture[key] = chip_smoke.digest(sim, summary)
        print(f"{key}: {fixture[key]['engine_rounds']} rounds, "
              f"{host_s:.1f} s on {chip_smoke.device_line()}", flush=True)
    with open(chip_smoke.FIXTURE, "w") as f:
        json.dump(dict(sorted(fixture.items())), f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
