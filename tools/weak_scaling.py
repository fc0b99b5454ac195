"""Weak-scaling curve for the tile-sharded engine (round 11).

Each cell of the (tile_shards, num_tiles) matrix runs the radix bench
shape (16 keys/tile, radix 64, ``tpu/block_events = 4``) for a warmed,
bounded window of quanta through the EXPLICIT shard_map path
(``tpu/tile_shards`` — parallel/mesh.shard_wrap; the GSPMD placement
this tool used in round 7 is superseded, PROFILE.md round 11) and
reports quanta per host second.  Every leg is its own subprocess with a
clean jax runtime on the parent's platform (the parent never imports
jax, so each leg may take the chip): with ``JAX_PLATFORMS=cpu`` it
forces exactly ``shards`` virtual devices, otherwise it uses the
devices jax exposes.

On CPU the collectives are loopback memcpy, so the curve bounds the
COORDINATION overhead of the sharded program from above rather than
demonstrating ICI bandwidth; the same invocation on a TPU slice
produces the real curve.

The summary is results_db-ingestible: one ``weak_scaling_shard{S}_T{T}``
workload per cell, each carrying ``quanta_per_s`` (tools/results_db.py
``add`` flags >20% drops per cell — like compares with like).

Round 15 adds RESIDENT rows (``tpu/shard_state = resident``): the same
matrix with state tile-sharded for the whole run and resolve home-routed
over two fixed-capacity all_to_alls, on a migratory chain workload.
Every row (both strategies) carries ``modeled_step_bytes_moved`` — the
modeled cross-device bytes of one quantum step's collectives — and
resident rows add ``resident_state_bytes_per_device`` (the O(T/S)
footprint claim in measurable form).

    python tools/weak_scaling.py                     # full curve
    python tools/weak_scaling.py --shards 1,8 --tiles 1024   # subset
    python tools/weak_scaling.py --no-resident       # replicated only
    python tools/weak_scaling.py --quanta 24 --warm 8        # window
    python tools/weak_scaling.py --leg S T           # internal (one cell)
    python tools/weak_scaling.py --leg-resident S T  # one resident cell

Env: ``GRAPHITE_WEAK_SCALING_BUDGET_S`` — wall-clock budget (default
3600); cells starting past it emit ``kind=skipped_budget`` rows instead
of silently shrinking the curve.
"""

import json
import os
import subprocess
import sys
import time

SHARDS = (1, 2, 4, 8)
TILES = (1024, 4096)
QUANTA = 24
WARM = 8
KEYS_PER_TILE = 16
RADIX = 64
DEFAULT_BUDGET_S = 3600.0


def _params(tiles: int, shards: int):
    from graphite_tpu.config import load_config
    from graphite_tpu.params import SimParams

    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("tpu/tile_shards", str(shards))
    cfg.set("tpu/block_events", 4)       # the bench radix1024 row config
    cfg.set("tpu/quanta_per_step", 1)
    return SimParams.from_config(cfg)


def _params_resident(tiles: int, shards: int):
    """Round-15 resident cells: tile-sharded state, home-routed resolve
    (the validated resident subset — chain engine on, window cache and
    DRAM queue model off)."""
    from graphite_tpu.config import load_config
    from graphite_tpu.params import SimParams

    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("tpu/tile_shards", str(shards))
    cfg.set("tpu/shard_state", "resident")
    cfg.set("tpu/block_events", 4)
    cfg.set("tpu/quanta_per_step", 1)
    cfg.set("tpu/miss_chain", 8)
    cfg.set("tpu/window_cache", "false")
    cfg.set("dram/queue_model/enabled", "false")
    return SimParams.from_config(cfg)


def _measure(shards: int, tiles: int, quanta: int, warm: int) -> dict:
    """Warm + timed megarun window of the radix shape at one cell."""
    import jax

    from graphite_tpu.engine.quantum import megarun
    from graphite_tpu.engine.state import TraceArrays, make_state
    from graphite_tpu.events import synth

    params = _params(tiles, shards)
    trace = synth.gen_radix(tiles, keys_per_tile=KEYS_PER_TILE,
                            radix=RADIX)
    tarrays = TraceArrays.from_trace(trace)
    state = make_state(params, has_capi=False)
    state = megarun(params, state, tarrays, warm)
    jax.block_until_ready(state)
    q0 = int(jax.device_get(state.ctr_quantum))
    t0 = time.perf_counter()
    state = megarun(params, state, tarrays, quanta)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    q1 = int(jax.device_get(state.ctr_quantum))
    from graphite_tpu.engine import resident as resident_mod
    bytes_moved = resident_mod.modeled_step_bytes(params, state)
    return {
        "kind": "completed",
        "mode": f"shard{shards}",
        "shard_state": "replicated",
        "tile_shards": shards,
        "devices": len(jax.devices()),
        "num_tiles": tiles,
        "timed_quanta": q1 - q0,
        "seconds": round(dt, 3),
        "quanta_per_s": round((q1 - q0) / max(dt, 1e-9), 3),
        "modeled_step_bytes_moved": bytes_moved["replicated"],
        "total_quanta": q1,
        "cursor_sum": int(jax.device_get(state.cursor.sum())),
        "workload": f"radix{tiles} weak-scaling window, "
                    f"{KEYS_PER_TILE} keys/tile",
    }


def _measure_resident(shards: int, tiles: int, quanta: int,
                      warm: int) -> dict:
    """Resident-mode cell: a migratory chain workload (the traffic shape
    home-routing is about — barrier-free, inside the resident subset)
    through engine/resident.megarun.  The modeled-bytes column compares
    the per-step collective payload of both strategies at this cell's
    geometry: replicated = the 13 window-output all_gathers' full-T
    leaves; resident = the two fixed-capacity all_to_alls per chain
    iteration."""
    import jax

    from graphite_tpu.engine import resident as resident_mod
    from graphite_tpu.engine.state import TraceArrays, make_state
    from graphite_tpu.events import synth

    params = _params_resident(tiles, shards)
    trace = synth.gen_migratory(tiles, lines=min(64, tiles * 2), rounds=2)
    tarrays = TraceArrays.from_trace(trace)
    state = make_state(params, has_capi=False)
    state = resident_mod.megarun(params, state, tarrays, warm)
    jax.block_until_ready(state)
    q0 = int(jax.device_get(state.ctr_quantum))
    t0 = time.perf_counter()
    state = resident_mod.megarun(params, state, tarrays, quanta)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    q1 = int(jax.device_get(state.ctr_quantum))
    bytes_moved = resident_mod.modeled_step_bytes(params, state)
    # Per-device resident HBM of the tile-sharded leaves: O(T/S).
    import numpy as np
    sharded_bytes = 0
    from graphite_tpu.parallel import mesh as meshmod
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        name = meshmod._path_name(path)
        if meshmod.resident_spec_for(name, leaf, tiles) \
                != meshmod.P():
            sharded_bytes += np.asarray(leaf).nbytes
    return {
        "kind": "completed",
        "mode": f"resident_shard{shards}",
        "shard_state": "resident",
        "tile_shards": shards,
        "devices": len(jax.devices()),
        "num_tiles": tiles,
        "timed_quanta": q1 - q0,
        "seconds": round(dt, 3),
        "quanta_per_s": round((q1 - q0) / max(dt, 1e-9), 3),
        "modeled_step_bytes_moved": bytes_moved["resident"],
        "modeled_step_bytes_moved_replicated": bytes_moved["replicated"],
        "resident_state_bytes_per_device": sharded_bytes // max(shards, 1),
        "total_quanta": q1,
        "cursor_sum": int(jax.device_get(state.cursor.sum())),
        "workload": f"migratory{tiles} resident weak-scaling window",
    }


def _leg_env(shards: int):
    """Env for one cell's child: the parent's platform, untouched (the
    parent never imports jax, so the child may take the chip).  Only
    when the caller pinned ``JAX_PLATFORMS=cpu`` does the child force
    exactly ``shards`` virtual CPU devices."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    if env.get("JAX_PLATFORMS") == "cpu":
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={shards}").strip()
    return repo, env


def run_leg(shards: int, tiles: int, quanta: int, warm: int,
            resident: bool = False) -> None:
    import graphite_tpu  # noqa: F401  (x64 + the compile-cache policy)
    fn = _measure_resident if resident else _measure
    print("WEAK_SCALING_ROW "
          + json.dumps(fn(shards, tiles, quanta, warm)), flush=True)


def shard_ab_row(shards: int, tiles: int = 1024, quanta: int = QUANTA,
                 warm: int = WARM) -> dict:
    """bench.py's ``radix1024_shard8`` A/B row, in the CALLER's process
    on the devices it sees: the cell sharded over ``shards`` devices
    and unsharded, both rates, and whether the final states match bit
    for bit."""
    import jax
    import jax.tree_util as jtu
    import numpy as np

    sharded = _measure(shards, tiles, quanta, warm)
    single = _measure(1, tiles, quanta, warm)

    # Bit-identity on a short full run of the same shape (quanta-bounded
    # so the check costs one more window, not a completion run).
    from graphite_tpu.engine.quantum import megarun
    from graphite_tpu.engine.state import TraceArrays, make_state
    from graphite_tpu.events import synth

    trace = synth.gen_radix(tiles, keys_per_tile=KEYS_PER_TILE,
                            radix=RADIX)
    tarrays = TraceArrays.from_trace(trace)

    def short(n):
        p = _params(tiles, n)
        st = megarun(p, make_state(p, has_capi=False), tarrays, warm)
        jax.block_until_ready(st)
        return st

    sS, s1 = short(shards), short(1)
    match = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jtu.tree_leaves(sS), jtu.tree_leaves(s1)))
    return {
        "kind": "completed",
        "num_tiles": tiles,
        "devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        "quanta_per_s": sharded["quanta_per_s"],
        "quanta_per_s_single": single["quanta_per_s"],
        f"shard{shards}_vs_single": round(
            sharded["quanta_per_s"]
            / max(single["quanta_per_s"], 1e-9), 3),
        "sharded_matches_single": bool(match),
        "timed_quanta": sharded["timed_quanta"],
        "workload": f"radix{tiles} shard{shards}-vs-single A/B, "
                    f"{KEYS_PER_TILE} keys/tile",
    }


def _subprocess_cell(args, shards: int, timeout: float) -> dict:
    repo, env = _leg_env(shards)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + args,
            capture_output=True, text=True, env=env, cwd=repo,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"kind": "skipped_budget",
                "error": f"cell exceeded {timeout:.0f}s"}
    rows = [l for l in out.stdout.splitlines()
            if l.startswith("WEAK_SCALING_ROW ")]
    if out.returncode != 0 or not rows:
        return {"kind": "failed",
                "error": (out.stdout + out.stderr)[-1500:]}
    return json.loads(rows[-1][len("WEAK_SCALING_ROW "):])


def _flag(argv, name, default):
    if name in argv:
        return argv[argv.index(name) + 1]
    return default


def main() -> int:
    argv = sys.argv[1:]
    quanta = int(_flag(argv, "--quanta", QUANTA))
    warm = int(_flag(argv, "--warm", WARM))
    if "--leg" in argv:
        i = argv.index("--leg")
        run_leg(int(argv[i + 1]), int(argv[i + 2]), quanta, warm)
        return 0
    if "--leg-resident" in argv:
        i = argv.index("--leg-resident")
        run_leg(int(argv[i + 1]), int(argv[i + 2]), quanta, warm,
                resident=True)
        return 0

    shards = [int(s) for s in
              str(_flag(argv, "--shards",
                        ",".join(map(str, SHARDS)))).split(",")]
    tiles = [int(t) for t in
             str(_flag(argv, "--tiles",
                       ",".join(map(str, TILES)))).split(",")]
    budget_s = float(os.environ.get("GRAPHITE_WEAK_SCALING_BUDGET_S",
                                    str(DEFAULT_BUDGET_S)))
    t_start = time.monotonic()
    detail = {}
    modes = [("", ["--leg"])]
    if "--no-resident" not in argv:
        modes.append(("resident_", ["--leg-resident"]))
    for t in tiles:
        for mode_tag, leg_flag in modes:
            for s in shards:
                label = f"weak_scaling_{mode_tag}shard{s}_T{t}"
                elapsed = time.monotonic() - t_start
                if elapsed > budget_s:
                    detail[label] = {"kind": "skipped_budget",
                                     "elapsed_s": round(elapsed, 1),
                                     "budget_s": budget_s}
                    print(f"{label}: skipped_budget", file=sys.stderr,
                          flush=True)
                    continue
                row = _subprocess_cell(
                    leg_flag + [str(s), str(t), "--quanta", str(quanta),
                                "--warm", str(warm)],
                    s, timeout=max(budget_s - elapsed, 60.0))
                detail[label] = row
                print(f"{label}: {row.get('quanta_per_s', row['kind'])}",
                      file=sys.stderr, flush=True)
    print(json.dumps({"metric": "weak_scaling", "detail": detail}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
