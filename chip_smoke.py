"""Bring-up smoke: the simulator's main path on a TPU, checked exactly.

Drives ``Simulator`` (the library entry point ``graphite-tpu run`` uses)
on seeded synthetic traces at BASELINE config 1's full width: SPLASH-2
radix, 64 tiles, the reference ``carbon_sim.cfg`` defaults.  The engine
is deterministic integer arithmetic, so every check is bit-exact.

    python chip_smoke.py             # one chip: phases a, b, c
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

Phases on one chip:
  a  goldens    the two ``tests/data/chain_off_golden.json`` cases
                (radix8, fft8; tests/test_block_equivalence.py) — per-tile
                clocks, every counter, every round counter
  b  radix64    the bench headline's trace (keys_per_tile=2048,
                radix=256) run to all_done; completion time, engine
                rounds and per-counter sums equal the CPU-recorded
                fixture ``tests/data/chip_smoke_fixture.json``
  c  probe1024  bench's radix1024 shape (keys_per_tile=16, radix=64,
                tpu/block_events=4) for a bounded number of steps, equal
                to the fixture — the north-star width

With ``--chips 4`` only the sharded phase runs, in this one process on
all four devices: ``tpu/tile_shards=4`` against 1 on the 1024-tile
probe, and ``tpu/shard_state=resident`` at 4 shards against 1 on a
migratory trace.  Final states must match leaf for leaf, and the
resident state's tile-sharded leaves must live on four devices.

Earlier lines report rounds, host seconds, compile seconds (every
compile since the previous line, set-up included), the device's
``bytes_in_use`` after the phase and its ``peak_bytes_in_use_so_far``
(the process's peak, which jax cannot reset between phases).  The last stdout line is one JSON object
``{"ok": true, "device": {...}}``.  Any failed check, or a backend that
is not a TPU, exits non-zero without that line; nothing falls back to
the CPU.  ``tools/record_smoke_fixture.py`` records the fixture on the
CPU; ``tests/test_chip_smoke.py`` runs these phases there at small sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "tests", "data", "chain_off_golden.json")
FIXTURE = os.path.join(HERE, "tests", "data", "chip_smoke_fixture.json")

ROUND_CTRS = ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
              "ctr_resolve", "round_ctr")

# Radix workloads by fixture key.  ``steps`` bounds a probe (megastep
# equivalents); None runs to completion.  The small entries are the
# tier-1 CPU test's stand-ins for the chip shapes.
HEADLINE = {
    "radix64": dict(tiles=64, keys_per_tile=2048, radix=256, steps=None),
    "radix8": dict(tiles=8, keys_per_tile=64, radix=16, steps=None),
}
PROBE = {
    "probe1024": dict(tiles=1024, keys_per_tile=16, radix=64, steps=2,
                      block_events=4),
    "probe16": dict(tiles=16, keys_per_tile=16, radix=64, steps=2,
                    block_events=4),
}
# Resident pair of the four-chip phase: the validated resident subset
# (tests/test_sharding.py _rparams) on line-migration traffic — radix
# carries barriers, which resident mode refuses.
RESIDENT = dict(tiles=64, lines=64, rounds=2)
RESIDENT_SETS = {"tpu/shard_state": "resident", "tpu/block_events": 4,
                 "tpu/quanta_per_step": 1, "tpu/miss_chain": 8,
                 "tpu/window_cache": "false",
                 "dram/queue_model/enabled": "false"}


class SmokeFailure(AssertionError):
    """A phase's result differs from its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling since the last
    ``take()`` (jax.monitoring's /jax/core/compile/* durations), while
    the ``with`` block is open."""

    def __init__(self):
        self.total = 0.0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration

    def take(self) -> float:
        t, self.total = self.total, 0.0
        return t


def workload_params(tiles: int, sets: dict):
    from graphite_tpu.config import load_config
    from graphite_tpu.params import SimParams
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    for k, v in sets.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


def radix_trace(w: dict):
    from graphite_tpu.events import synth
    return synth.gen_radix(num_tiles=w["tiles"],
                           keys_per_tile=w["keys_per_tile"],
                           radix=w["radix"])


def probe_sets(w: dict) -> dict:
    return {"tpu/block_events": w["block_events"]} \
        if "block_events" in w else {}


def digest(sim, summary) -> dict:
    """What a fixture pins: completion, round counters, progress sums,
    and every counter summed over tiles."""
    import jax
    import numpy as np
    st = jax.device_get({f: getattr(sim.state, f)
                         for f in ROUND_CTRS + ("clock", "cursor")})
    return {
        "all_done": bool(summary.done.all()),
        "completion_time_ps": summary.completion_time_ps,
        "engine_rounds": int(st["round_ctr"]),
        "round_ctrs": {f: int(st[f]) for f in ROUND_CTRS},
        "clock_sum": int(np.asarray(st["clock"]).sum()),
        "cursor_sum": int(np.asarray(st["cursor"], np.int64).sum()),
        "counters": {k: int(v.sum()) for k, v in summary.counters.items()},
    }


def _compare(got: dict, want: dict, label: str) -> None:
    for k, v in want.items():
        if k == "counters":
            for c, n in v.items():
                check(got["counters"].get(c) == n,
                      f"{label}.{c}: {got['counters'].get(c)} != {n}")
        else:
            check(got.get(k) == v, f"{label}.{k}: {got.get(k)} != {v}")


def device_line() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def report(phase: str, clock: CompileClock, **fields) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    fields["compile_s"] = round(clock.take(), 3)
    fields["bytes_in_use"] = stats.get("bytes_in_use")
    fields["peak_bytes_in_use_so_far"] = stats.get("peak_bytes_in_use")
    print(f"{phase}: " + json.dumps(fields), flush=True)


def run_workload(params, trace, steps):
    """One warm-up window (compiles the program) then the timed run;
    returns (sim, summary, host seconds of the timed run)."""
    from graphite_tpu.engine.sim import Simulator
    Simulator(params, trace).run(max_steps=1)
    sim = Simulator(params, trace)
    t0 = time.perf_counter()
    summary = sim.run(max_steps=steps)
    return sim, summary, time.perf_counter() - t0


# ------------------------------------------------------------- phases

def phase_goldens(clock: CompileClock) -> None:
    """(a) The committed miss_chain=0 goldens, bit for bit."""
    import jax
    import numpy as np

    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.events import synth

    with open(GOLDEN) as f:
        gold = json.load(f)
    cases = {
        "radix8": synth.gen_radix(num_tiles=8, keys_per_tile=64,
                                  radix=16, seed=3),
        "fft8": synth.gen_fft(num_tiles=8, points_per_tile=64),
    }
    for name, trace in cases.items():
        g = gold[name]
        t0 = time.perf_counter()
        sim = Simulator(workload_params(8, {"tpu/miss_chain": 0}),
                        trace)
        s = sim.run(max_steps=256)
        host_s = time.perf_counter() - t0
        st = jax.device_get({f: getattr(sim.state, f) for f in ROUND_CTRS})
        check(bool(s.done.all()), f"{name}: not all done")
        check(s.completion_time_ps == g["completion_time_ps"],
              f"{name}: completion {s.completion_time_ps} != "
              f"{g['completion_time_ps']}")
        check(np.asarray(s.clock).tolist() == g["clock"],
              f"{name}: per-tile clocks differ")
        for f, want in g["round_ctrs"].items():
            got = int(st[f])
            check(got == want, f"{name}.{f}: {got} != golden {want}")
        for k, want in g["counters"].items():
            check(np.asarray(s.counters[k]).tolist() == want,
                  f"{name}.{k}: counters differ")
        report(f"a.golden_{name}", clock, rounds=int(st["round_ctr"]),
               host_s_incl_compile=round(host_s, 3))


def phase_headline(clock: CompileClock, key: str = "radix64") -> dict:
    """(b) The radix headline to completion, equal to the fixture."""
    w = HEADLINE[key]
    sim, s, host_s = run_workload(
        workload_params(w["tiles"], probe_sets(w)), radix_trace(w),
        w["steps"])
    got = digest(sim, s)
    with open(FIXTURE) as f:
        _compare(got, json.load(f)[key], key)
    check(got["all_done"], f"{key}: not all done")
    report(f"b.{key}", clock, rounds=got["engine_rounds"],
           quanta=got["round_ctrs"]["ctr_quantum"], host_s=round(host_s, 3),
           completion_time_ps=got["completion_time_ps"],
           instructions=s.total_instructions,
           simulated_mips=round(s.total_instructions / host_s / 1e6, 4))
    return got


def phase_probe(clock: CompileClock, key: str = "probe1024") -> dict:
    """(c) A bounded run at the probe width, equal to the fixture."""
    w = PROBE[key]
    sim, s, host_s = run_workload(
        workload_params(w["tiles"], probe_sets(w)), radix_trace(w),
        w["steps"])
    got = digest(sim, s)
    with open(FIXTURE) as f:
        _compare(got, json.load(f)[key], key)
    check(got["engine_rounds"] > 0, f"{key}: no rounds ran")
    report(f"c.{key}", clock, rounds=got["engine_rounds"],
           quanta=got["round_ctrs"]["ctr_quantum"], host_s=round(host_s, 3),
           ms_per_round=round(host_s / got["engine_rounds"] * 1e3, 3),
           instructions=s.total_instructions)
    return got


def _states_equal(a, b, label: str) -> None:
    import jax
    import numpy as np
    la = jax.tree_util.tree_leaves_with_path(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    check(len(la) == len(lb), f"{label}: leaf counts differ")
    for (path, x), y in zip(la, lb):
        check(np.array_equal(np.asarray(x), np.asarray(y)),
              f"{label}: leaf {jax.tree_util.keystr(path)} differs")


def phase_sharded(clock: CompileClock, shards: int = 4,
                  probe: str = "probe1024", resident=RESIDENT) -> None:
    """The multi-chip path: replicated tile_shards=S vs 1 on the probe,
    resident S vs 1 on migratory traffic; final states leaf-identical,
    the resident state's tile-sharded leaves spread over S devices."""
    import jax

    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.events import synth
    from graphite_tpu.parallel import mesh as meshmod

    check(len(jax.devices()) >= shards,
          f"needs {shards} devices; jax sees {len(jax.devices())}")
    # Each run's final state comes to the host and its simulator goes
    # before the next run, so a line's bytes_in_use is its own run's.
    w = PROBE[probe]
    trace = radix_trace(w)
    final = {}
    for n in (shards, 1):
        p = workload_params(w["tiles"],
                            {**probe_sets(w), "tpu/tile_shards": n})
        sim, s, host_s = run_workload(p, trace, w["steps"])
        report(f"s.{probe}_tile_shards{n}", clock,
               rounds=int(jax.device_get(sim.state.round_ctr)),
               host_s=round(host_s, 3))
        final[n] = jax.device_get(sim.state)
        del sim, s
    _states_equal(final[shards], final[1],
                  f"{probe} tile_shards={shards} vs 1")

    T = resident["tiles"]
    trace = synth.gen_migratory(T, lines=resident["lines"],
                                rounds=resident["rounds"])
    spread = 0
    for n in (shards, 1):
        p = workload_params(T, {**RESIDENT_SETS, "tpu/tile_shards": n})
        t0 = time.perf_counter()
        sim = Simulator(p, trace)
        s = sim.run()
        check(bool(s.done.all()), f"resident S={n}: not all done")
        report(f"s.resident_migratory{T}_S{n}", clock, quanta=s.quanta,
               host_s_incl_compile=round(time.perf_counter() - t0, 3),
               completion_time_ps=s.completion_time_ps)
        if n == shards:
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                    sim.state):
                name = meshmod._path_name(path)
                if meshmod.resident_spec_for(name, leaf, T) == meshmod.P():
                    continue
                devs = {sh.device for sh in leaf.addressable_shards}
                check(len(devs) == shards and len(leaf.sharding.device_set)
                      == shards,
                      f"resident leaf {name} spans {len(devs)} devices")
                spread += 1
        final[n] = jax.device_get(sim.state)
        del sim, s
    check(spread > 0, "no resident leaf is tile-sharded")
    _states_equal(final[shards], final[1], f"resident S={shards} vs 1")
    print(f"s.resident_spread: {spread} tile-sharded leaves on "
          f"{shards} devices each", flush=True)


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase on four chips")
    args = ap.parse_args(argv)

    import graphite_tpu
    if os.path.dirname(os.path.abspath(graphite_tpu.__file__)) \
            != os.path.join(HERE, "graphite_tpu"):
        print(f"chip_smoke: graphite_tpu imported from "
              f"{graphite_tpu.__file__}, not this checkout",
              file=sys.stderr)
        return 2
    import jax
    dev = device_line()
    print(f"device: {json.dumps(dev)}  compile cache: "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev['platform']}",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{dev['count']} devices", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    with CompileClock() as clock:
        if args.chips == 4:
            phase_sharded(clock)
        else:
            phase_goldens(clock)
            phase_headline(clock)
            phase_probe(clock)
    print(f"total: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
