#!/usr/bin/env bash
# Chunked test runner: one pytest process per test module (VERDICT r2 weak
# #7 — a single-process full-suite run accumulates JAX compile cache /
# interpreter state until it crashes; per-module isolation sidesteps that
# and the persistent compile cache in tests/conftest.py keeps re-runs
# fast).
#
# Usage: tools/run_tests.sh [-m marker_expr] [pytest args...]
set -u
cd "$(dirname "$0")/.."
fail=0
total_pass=0
total_fail=0
chain_out=""
chain_rc=5
for f in tests/test_*.py; do
    out=$(timeout 1800 python -m pytest "$f" -q -rxX "$@" 2>&1)
    rc=$?
    line=$(echo "$out" | grep -E "^[0-9]+ (passed|failed)|passed|failed|error" | tail -1)
    echo "$f: $line"
    if [ $rc -ne 0 ] && [ $rc -ne 5 ]; then   # 5 = no tests collected (marker filter)
        fail=1
        echo "$out" | tail -30
    fi
    # The chain-oracle gate below inspects this module's outcome
    # classes without re-running it.
    if [ "$f" = "tests/test_chain_equivalence.py" ]; then
        chain_out="$out"
        chain_rc=$rc
    fi
done
# Telemetry smoke: run a tiny trace through the CLI with --telemetry-dir
# and validate that the RunReport + Chrome-trace artifacts parse (exports
# must not silently rot; ISSUE 2 CI satellite).
tel_dir=$(mktemp -d)
tel_out=$(timeout 1800 python - "$tel_dir" <<'PYEOF' 2>&1
import json, os, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
tel_dir = sys.argv[1]
from graphite_tpu.events import synth
trace_path = os.path.join(tel_dir, "smoke.npz")
synth.gen_radix(2, keys_per_tile=16, radix=8).save(trace_path)
from graphite_tpu.cli import main
# interval 500 ns < the 1000 ns quantum, so every quantum samples and
# even this tiny trace yields round-metric rows
rc = main(["--telemetry/interval=500", "run", "--trace", trace_path,
           "--telemetry-dir", tel_dir,
           "-o", os.path.join(tel_dir, "sim.out")])
assert rc == 0, f"cli rc={rc}"
report = json.load(open(os.path.join(tel_dir, "run_report.json")))
assert report["schema"].startswith("graphite_tpu/run_report")
assert report["counters"]["icount"] > 0 and report["telemetry"]["time_ps"]
ct = json.load(open(os.path.join(tel_dir, "run_trace.json")))
events = ct["traceEvents"]
assert any(e["ph"] == "X" and "ts" in e and "pid" in e and "tid" in e
           for e in events), "no X slices in trace export"
print("TELEMETRY SMOKE OK")
PYEOF
)
tel_rc=$?
echo "$tel_out" | tail -3
rm -rf "$tel_dir"
if [ $tel_rc -ne 0 ]; then
    fail=1
fi

# Budgeted perf smoke (ISSUE 3 CI satellite): a tiny trace must clear a
# conservative rounds/s floor (catches accidental 10x round-cost
# regressions, not noise), and bench.py's --help and budget/emit
# protocol must work without device work (stubbed rows), so a driver
# bench can never again die numberless to a timeout.
perf_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import io, json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())
from graphite_tpu.config import load_config
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

cfg = load_config()
cfg.set("general/total_cores", 2)
params = SimParams.from_config(cfg)
trace = synth.gen_radix(2, keys_per_tile=32, radix=8)
Simulator(params, trace).run()            # warm the compile cache
sim = Simulator(params, trace)
t0 = time.perf_counter()
sim.run()
dt = time.perf_counter() - t0
import jax
rounds = int(jax.device_get(sim.state.round_ctr))
rps = rounds / max(dt, 1e-9)
assert rps > 5.0, f"rounds/s floor: {rps:.1f} <= 5 ({rounds} rounds in {dt:.2f}s)"
print(f"perf smoke: {rps:.0f} rounds/s ({rounds} rounds, {dt:.2f}s)")

# bench.py budget protocol, no device work: stub the row runners and
# force budget 0 — every non-headline row must emit skipped_budget and
# the line must re-print incrementally.
import bench
stub = {"kind": "completed", "num_tiles": 64, "mips": 1.0,
        "total_instructions": 1, "host_seconds": 0.01}
bench._run = lambda *a, **k: dict(stub)
bench._captured_row = lambda name: {"kind": "skipped", "reason": "stub"}
os.environ["GRAPHITE_BENCH_BUDGET_S"] = "0"
buf = io.StringIO()
from contextlib import redirect_stdout
with redirect_stdout(buf):
    rc = bench.main([])
assert rc == 0, f"bench.main rc={rc}"
lines = [l for l in buf.getvalue().splitlines() if l.strip()]
assert len(lines) >= 5, f"bench must re-emit per row, got {len(lines)} lines"
out = json.loads(lines[-1])
skipped = [k for k, v in out["detail"].items()
           if isinstance(v, dict) and v.get("kind") == "skipped_budget"]
assert skipped, f"budget=0 must skip rows: {out['detail'].keys()}"
assert json.loads(lines[0])["metric"] == "simulated_mips_radix64"
print(f"bench budget smoke: {len(lines)} emits, {len(skipped)} skipped_budget rows")
PYEOF
)
perf_rc=$?
echo "$perf_out" | tail -3
if [ $perf_rc -ne 0 ]; then
    fail=1
fi
if ! timeout 60 python bench.py --help > /dev/null 2>&1; then
    echo "bench.py --help FAILED"
    fail=1
fi

# Round-count budget smoke (ISSUE 9 CI satellite): the radix-8 probe
# trace must finish under a FIXED round ceiling.  Rounds are exact and
# deterministic (no host noise), so the ceiling is a hard gate the way
# the chain-oracle gate refuses xfails: the round-9 engine retires this
# trace in 86 rounds, the round-8 cadence took 92, so a ceiling of 90
# refuses any regression of the boundary-spanning/fan-out cadence —
# including a silent flip of the tpu/fanout_replay default.
budget_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from graphite_tpu.config import load_config
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

ROUND_CEILING = 90
cfg = load_config()
cfg.set("general/total_cores", 8)
cfg.set("tpu/miss_chain", 12)
params = SimParams.from_config(cfg)
# Same shape as the chain-oracle equality gate -> persistent-cache hit.
trace = synth.gen_radix(num_tiles=8, keys_per_tile=64, radix=16, seed=3)
sim = Simulator(params, trace)
s = sim.run(max_steps=512)
assert s.done.all(), "round-budget trace did not complete"
rounds = int(jax.device_get(sim.state.round_ctr))
assert rounds <= ROUND_CEILING, (
    f"ROUND BUDGET EXCEEDED: {rounds} > {ROUND_CEILING} (round-9 "
    f"cadence retires this trace in 86; 92 is the round-8 engine)")
print(f"ROUND BUDGET SMOKE OK ({rounds} rounds <= {ROUND_CEILING})")
PYEOF
)
budget_rc=$?
echo "$budget_out" | tail -3
if [ $budget_rc -ne 0 ]; then
    echo "ROUND BUDGET GATE FAILED"
    fail=1
fi

# Sweep smoke gate (ISSUE 7 CI satellite): a two-variant tiny sweep must
# run through the driver with EXACTLY ONE XLA compile for the bucket
# (batch.compile_count() counts jit traces == in-process compile
# requests; variant values leaking into the static argument would show
# as a second trace), and each lane must match its solo run bit-exactly.
sweep_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from graphite_tpu.config import load_config
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.sweep import SweepDriver, build_variants
from graphite_tpu.sweep import batch as batchmod

cfg = load_config()
cfg.set("general/total_cores", 2)
trace = synth.gen_radix(2, keys_per_tile=16, radix=8)
variants = build_variants(cfg, ["dram/latency=80,140"])
before = batchmod.compile_count()
drv = SweepDriver(trace)
tickets = [drv.submit(p) for _, _, p in variants]
results = drv.drain()
compiles = batchmod.compile_count() - before
assert compiles == 1, f"bucket compiled {compiles} programs, expected 1"
for (label, _, p), t in zip(variants, tickets):
    lane, solo = results[t], Simulator(p, trace).run()
    assert np.array_equal(lane.clock, solo.clock), label
    for k in lane.counters:
        assert np.array_equal(lane.counters[k], solo.counters[k]), \
            f"{label}.{k}"
print(f"SWEEP SMOKE OK ({compiles} compile, "
      f"{len(tickets)} variants bit-identical to serial)")
PYEOF
)
sweep_rc=$?
echo "$sweep_out" | tail -3
if [ $sweep_rc -ne 0 ]; then
    echo "SWEEP SMOKE GATE FAILED"
    fail=1
fi

# Pallas-kernel smoke gate (ISSUE 10 CI satellite): (1) the dispatch
# layer must select the lax path under the default "off" (Mosaic
# refuses the kernels' int64 operands, ROADMAP A.1); (2) a tiny-shape interpret run must be BIT-IDENTICAL to
# the lax run — clocks, every counter, every phase-execution counter —
# through the whole engine including the chain replay's classify
# kernel; (3) the window phase with kernels on must lower to exactly
# ONE pallas_call equation (the single-custom-call contract results_db
# tracks as lowered_window_calls).
pallas_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import dataclasses
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import numpy as np
from graphite_tpu.config import load_config
from graphite_tpu.engine import core
from graphite_tpu.engine.kernels import dispatch as kdispatch
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.engine.vparams import variant_params
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

trace = synth.gen_radix(2, keys_per_tile=24, radix=8, seed=3)

def run(mode):
    cfg = load_config()
    cfg.set("general/total_cores", 2)
    cfg.set("tpu/miss_chain", 4)
    cfg.set("tpu/pallas_kernels", mode)
    params = SimParams.from_config(cfg)
    sim = Simulator(params, trace)
    s = sim.run(max_steps=64)
    return params, sim, s

p_def = SimParams.from_config(load_config())
assert p_def.pallas_kernels == "off"
assert kdispatch.kernels_mode(p_def) == "off", \
    "the default must be the lax path"

pa, sa, a = run("off")
pb, sb, b = run("interpret")
assert a.done.all() and b.done.all()
assert np.array_equal(a.clock, b.clock), "clocks diverge"
for k in a.counters:
    assert np.array_equal(a.counters[k], b.counters[k]), k
for f in ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
          "ctr_resolve", "round_ctr"):
    va, vb = int(getattr(sa.state, f)), int(getattr(sb.state, f))
    assert va == vb, f"{f}: {va} != {vb}"

vp = variant_params(pb)
c = kdispatch.jaxpr_op_counts(
    lambda s: core._block_retire(pb, vp, s, sb.trace), sb.state)
assert c["pallas_call"] == 1, f"window phase must be ONE call: {c}"
print(f"PALLAS SMOKE OK (interpret bit-identical, "
      f"{int(sa.state.round_ctr)} rounds, window pallas_call=1)")
PYEOF
)
pallas_rc=$?
echo "$pallas_out" | tail -3
if [ $pallas_rc -ne 0 ]; then
    echo "PALLAS SMOKE GATE FAILED"
    fail=1
fi

# Chain-oracle gate (ISSUE 6): the blocking-semantics miss-chain engine
# must match the one-parked-request oracle within 2% — these equality
# tests were xfail documentation of the round-4 MSHR machine's
# behavioral gap and are now hard gates.  The module already ran once
# in the loop above (-rxX reports outcome classes, honoring this
# invocation's marker tier — T=8 shapes by default, T=64 under
# -m slow); here its captured output is REFUSED on any xfail/xpass
# outcome, so a future regression to non-blocking behavior (or a
# re-xfail of the tests) cannot ship silently.
if [ $chain_rc -eq 5 ]; then
    # rc 5 = nothing collected (also the sentinel for "module never
    # ran") — legitimate only under an explicit marker/keyword filter;
    # say so loudly instead of passing silently.
    echo "chain-oracle gate: SKIPPED (no chain tests collected in this" \
         "tier — the default tier always collects them)"
elif [ $chain_rc -ne 0 ]; then
    echo "chain-oracle gate: $(echo "$chain_out" | grep -E "passed|failed|error" | tail -1)"
    echo "CHAIN ORACLE GATE FAILED"
    fail=1
elif echo "$chain_out" | grep -qE "xfailed|xpassed"; then
    echo "$chain_out" | tail -10
    echo "CHAIN ORACLE GATE FAILED (xfail markers are not allowed here)"
    fail=1
else
    line=$(echo "$chain_out" | grep -E "passed|failed|error" | tail -1)
    echo "chain-oracle gate: $line"
    # The quick tier holds 7 chain tests (2 equality gates + 3
    # invariants + the migratory drift pin + the fan-out round-drop
    # canary); fewer passing means one was slow-marked/skipped out
    # of the tier — deselection must be as loud as an xfail.
    npass=$(echo "$line" | grep -oE "^[0-9]+" | head -1)
    if [ "${npass:-0}" -lt 7 ]; then
        echo "CHAIN ORACLE GATE FAILED (only ${npass:-0} chain tests ran" \
             "in this tier; the 2 equality gates + 3 invariants + the 2" \
             "round-9 canaries must all run)"
        fail=1
    fi
fi

# Sharding smoke gate (ISSUE 11 CI satellite): over 8 virtual devices a
# tiny trace must (1) run bit-identically with tpu/tile_shards=8 vs 1
# (every state leaf), and (2) lower the PER-SHARD window phase with
# ZERO collective primitives — the scale-out claim's structural form:
# the walk is shard-local compute, cross-device traffic exists only in
# the step's explicit all_gathers + pmin (counted, bounded).
shard_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
import jax
import numpy as np
from graphite_tpu.config import load_config
from graphite_tpu.engine import quantum
from graphite_tpu.engine.kernels import dispatch
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

def params(shards):
    cfg = load_config()
    cfg.set("general/total_cores", 16)
    cfg.set("tpu/tile_shards", str(shards))
    return SimParams.from_config(cfg)

trace = synth.gen_radix(16, keys_per_tile=8, radix=8)
p8, p1 = params(8), params(1)
s8 = Simulator(p8, trace); s8.run()
s1 = Simulator(p1, trace); s1.run()
leaves8 = jax.tree_util.tree_leaves(s8.state)
leaves1 = jax.tree_util.tree_leaves(s1.state)
assert len(leaves8) == len(leaves1)
for a, b in zip(leaves8, leaves1):
    assert np.array_equal(np.asarray(a), np.asarray(b))

c8 = dispatch.jaxpr_op_counts(
    lambda s, t: quantum.megastep(p8, s, t), s1.state, s1.trace)
c1 = dispatch.jaxpr_op_counts(
    lambda s, t: quantum.megastep(p1, s, t), s1.state, s1.trace)
assert c1["collective"] == 0, c1
assert 0 < c8["collective"] <= 64, c8

# Per-shard window phase: slice to the shard's tiles, walk — zero
# collectives and no full-T gather (every aval's tile axis is T/S).
from graphite_tpu.engine import core
from graphite_tpu.engine.kernels import window as kwindow
from graphite_tpu.engine.vparams import variant_params
vp = variant_params(p1)
captured = {}
orig = kwindow.run_window
def spy(params, vp2, wi, s_ids, mode):
    captured["wi"] = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wi)
    captured["s_ids"] = s_ids
    return orig(params, vp2, wi, s_ids, mode)
kwindow.run_window = spy
jax.eval_shape(lambda s: core._block_retire(p1, vp, s, s1.trace), s1.state)
kwindow.run_window = orig
def walk_local(wi):
    wi_l = kwindow.shard_local_window_in(wi, 0, 16 // 8)
    return kwindow.window_walk(p8, vp, wi_l, captured["s_ids"])
cw = dispatch.jaxpr_op_counts(walk_local, captured["wi"])
assert cw["collective"] == 0, cw
print(f"SHARDING SMOKE OK (8v1 bit-identical; step collectives "
      f"{c8['collective']} sharded / {c1['collective']} solo; "
      f"per-shard walk 0)")
PYEOF
)
shard_rc=$?
echo "$shard_out" | tail -3
if [ $shard_rc -ne 0 ]; then
    echo "SHARDING SMOKE GATE FAILED"
    fail=1
fi

# Resident routed-resolve gate (ISSUE 19 CI satellite): the lowered
# resident quantum step (tpu/shard_state=resident over 8 virtual
# devices) must contain ZERO full-T all_gathers, at most TWO
# fixed-capacity all_to_alls (request + response routing legs) and
# exactly ONE int64 pmin (the quantum barrier, as two int32 pmin
# words).  Both censuses are recorded keyed by shard strategy in results_db, whose COUNT_METRICS flag must
# fire if a resident row ever grows a collective — a full-T
# materialization leaking back into the steady state is a 0 -> 1
# event, not a drift.
resident_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import os, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
from graphite_tpu.config import load_config
from graphite_tpu.engine import quantum, resident
from graphite_tpu.engine.kernels import dispatch
from graphite_tpu.engine.state import TraceArrays, make_state
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

def params(shard_state):
    cfg = load_config()
    cfg.set("general/total_cores", 16)
    cfg.set("tpu/tile_shards", "8")
    cfg.set("tpu/shard_state", shard_state)
    if shard_state == "resident":
        cfg.set("tpu/block_events", "4")
        cfg.set("tpu/quanta_per_step", "1")
        cfg.set("tpu/miss_chain", "8")
        cfg.set("tpu/window_cache", "false")
        cfg.set("dram/queue_model/enabled", "false")
    return SimParams.from_config(cfg)

trace = synth.gen_migratory(16, lines=4, rounds=2)
tarrays = TraceArrays.from_trace(trace)

pres = params("resident")
cres = resident.lowered_quantum_collectives(
    pres, make_state(pres), tarrays)
assert cres["all_gather"] == 0, cres
assert cres["all_to_all"] <= 2, cres
assert cres["pmin"] == 2, cres   # one int64 barrier = two int32 words

prep = params("replicated")
crep = dispatch.jaxpr_op_counts(
    lambda s, t: quantum.megastep(prep, s, t),
    make_state(prep), tarrays)
assert crep["all_gather"] > 0, crep   # what the resident step deleted

sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
import results_db
tmp = tempfile.mkdtemp()
rdb = results_db.open_db(os.path.join(tmp, "census.db"))
row = {"lowered_step_collectives_replicated": crep["collective"],
       "lowered_step_collectives_resident": cres["collective"],
       "lowered_step_all_gathers_resident": cres["all_gather"],
       "lowered_step_all_to_alls_resident": cres["all_to_all"]}
assert results_db.check_regression(rdb, "resident_census", row) is None
results_db.add_run(rdb, "resident_census", row)
grown = dict(row)
grown["lowered_step_all_to_alls_resident"] += 1
warn = results_db.check_regression(rdb, "resident_census", grown)
assert warn and "lowered_step_all_to_alls_resident" in warn, warn
print(f"RESIDENT ROUTED-RESOLVE GATE OK (resident step: "
      f"{cres['all_gather']} all_gathers / {cres['all_to_all']} "
      f"all_to_alls / {cres['pmin']} pmin, {cres['collective']} "
      f"collectives total; replicated step: {crep['all_gather']} "
      f"all_gathers; census regression flag fires)")
PYEOF
)
resident_rc=$?
echo "$resident_out" | tail -3
if [ $resident_rc -ne 0 ]; then
    echo "RESIDENT ROUTED-RESOLVE GATE FAILED"
    fail=1
fi

# Fast-forward smoke gate (ISSUE 14 CI satellite): the adaptive-fidelity
# analytic leg on the tiny radix-8 trace must (1) leave fast_forward=0
# EXACTLY on the committed golden fixture (the leg is compiled in only
# when the knob is > 0 — the default engine cannot drift), (2) engage
# and strictly CUT the engine round count with the leg on (rounds are
# exact and deterministic, so a strict drop is a hard floor, not a
# noisy ratio), and (3) hold the completion-time drift under the 2%
# accuracy budget — the same ceiling the bench *_ff rows and
# results_db's DRIFT flag enforce.
ff_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from graphite_tpu.config import load_config
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

DRIFT_CEILING = 0.02

def run(ff):
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    cfg.set("tpu/fast_forward", ff)
    params = SimParams.from_config(cfg)
    sim = Simulator(params, trace)
    s = sim.run(max_steps=256)
    assert s.done.all(), f"ff={ff} smoke trace did not complete"
    return sim, s

# Same shape as the golden fixture -> persistent-cache hit.
trace = synth.gen_radix(num_tiles=8, keys_per_tile=64, radix=16, seed=3)
gold = json.load(open("tests/data/fast_forward_golden.json"))["radix8"]
sim0, s0 = run(0)
assert s0.completion_time_ps == gold["completion_time_ps"], \
    "fast_forward=0 completion drifted off the golden fixture"
assert int(sim0.state.round_ctr) == gold["round_ctrs"]["round_ctr"], \
    "fast_forward=0 round count drifted off the golden fixture"
sim4, s4 = run(4)
r0 = int(jax.device_get(sim0.state.round_ctr))
r4 = int(jax.device_get(sim4.state.round_ctr))
assert int(sim4.state.ctr_ff) > 0, "analytic leg never engaged"
assert r4 < r0, f"ROUND DROP FLOOR: ff rounds {r4} !< exact {r0}"
drift = abs(s4.completion_time_ps - s0.completion_time_ps) \
    / max(s0.completion_time_ps, 1)
assert drift <= DRIFT_CEILING, (
    f"DRIFT CEILING: {drift:.2%} > {DRIFT_CEILING:.0%}")
print(f"FAST-FORWARD SMOKE OK (rounds {r0} -> {r4}, "
      f"{int(sim4.state.ctr_ff)} analytic rounds, drift {drift:.2%})")
PYEOF
)
ff_rc=$?
echo "$ff_out" | tail -3
if [ $ff_rc -ne 0 ]; then
    echo "FAST-FORWARD SMOKE GATE FAILED"
    fail=1
fi

# Kill-and-recover gate (ISSUE 15 CI satellite): a serving process is
# SIGKILLed mid-bucket by the fault harness (GRAPHITE_FAULTS is
# inherited through the environment — no cleanup, no atexit, the honest
# crash); a restart with --resume must recover the journal, re-queue the
# interrupted tickets, and produce per-lane summaries BIT-IDENTICAL to
# an uninterrupted reference serve in a fresh journal.
recover_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import json, os, shutil, signal, subprocess, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from graphite_tpu.events import synth

tmp = tempfile.mkdtemp()
trace_path = os.path.join(tmp, "t.npz")
synth.gen_radix(2, keys_per_tile=16, radix=8, seed=1).save(trace_path)

BASE = [sys.executable, "-c",
        "from graphite_tpu.cli import main; raise SystemExit(main())",
        # 100ns barrier quantum + 1-step windows: the tiny trace spans
        # several window boundaries, so the 2nd-window SIGKILL lands
        # genuinely mid-bucket.
        "--general/total_cores=2",
        "--clock_skew_management/lax_barrier/quantum=100",
        "--service/poll_every=1",
        "sweep", "--trace", trace_path, "--serve"]
SWEEP = ["--sweep", "dram/latency=90,120"]

def serve(journal, out, extra, env_faults=None):
    env = dict(os.environ)
    env.pop("GRAPHITE_FAULTS", None)
    if env_faults:
        env["GRAPHITE_FAULTS"] = env_faults
    cmd = BASE + ["--journal", journal, "-o", out] + extra
    return subprocess.run(cmd, env=env, cwd=os.getcwd(),
                          capture_output=True, text=True, timeout=900)

# Reference leg: uninterrupted serve in its own journal.
ref_out = os.path.join(tmp, "ref.json")
r = serve(os.path.join(tmp, "jref"), ref_out, SWEEP)
assert r.returncode == 0, r.stderr[-2000:]
ref = json.load(open(ref_out))["detail"]
assert ref and all(v["status"] == "done" for v in ref.values())

# Kill leg: the armed harness SIGKILLs the process at the 2nd window.
jkill = os.path.join(tmp, "jkill")
kill_out = os.path.join(tmp, "kill.json")
k = serve(jkill, kill_out, SWEEP, env_faults="sigkill_in_bucket:2")
assert k.returncode in (-signal.SIGKILL, 128 + signal.SIGKILL), \
    f"expected SIGKILL death, rc={k.returncode}\n{k.stderr[-2000:]}"
assert not os.path.exists(kill_out), \
    "killed leg must die before emitting results"

# Recovery leg: restart over the same journal (--resume re-queues the
# in-flight tickets; no --sweep — the journal is the work source).
rec_out = os.path.join(tmp, "rec.json")
r2 = serve(jkill, rec_out, ["--resume"])
assert r2.returncode == 0, r2.stderr[-2000:]
rec = json.load(open(rec_out))
assert rec["stats"]["recovered"] >= 1, rec["stats"]
det = rec["detail"]
assert set(det) == set(ref)
for label, row in ref.items():
    assert det[label]["status"] == "done", (label, det[label])
    assert det[label]["clock_ps"] == row["clock_ps"], \
        f"{label}: recovered lane diverged from the uninterrupted serve"
    assert det[label]["quanta"] == row["quanta"], label
shutil.rmtree(tmp)
print(f"KILL-AND-RECOVER SMOKE OK ({len(det)} tickets bit-identical "
      f"after SIGKILL mid-bucket; {rec['stats']['recovered']} requeued)")
PYEOF
)
recover_rc=$?
echo "$recover_out" | tail -3
if [ $recover_rc -ne 0 ]; then
    echo "KILL-AND-RECOVER GATE FAILED"
    fail=1
fi

# Service-observability smoke gate (ISSUE 17 CI satellite): serve one
# ticket to seed the results_db, then serve TWO tickets (the seeded one
# + a fresh one) in a second process with --metrics-path.  The written
# Prometheus exposition must PARSE, count exactly 2 ticket_latency_s
# observations and exactly 1 cache_hits_total, and the serve output +
# journal must carry the streaming evidence (p99_first_result_s,
# first_result records preceding done records) plus a working `status`
# subcommand over the journal.
obs_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import json, os, shutil, subprocess, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from graphite_tpu.events import synth
from graphite_tpu.obs.registry import parse_exposition

tmp = tempfile.mkdtemp()
trace_path = os.path.join(tmp, "t.npz")
synth.gen_radix(2, keys_per_tile=16, radix=8, seed=1).save(trace_path)
db = os.path.join(tmp, "results.db")
metrics = os.path.join(tmp, "metrics.prom")

BASE = [sys.executable, "-c",
        "from graphite_tpu.cli import main; raise SystemExit(main())",
        "--general/total_cores=2"]

def run(args):
    env = dict(os.environ)
    env.pop("GRAPHITE_FAULTS", None)
    r = subprocess.run(BASE + args, env=env, cwd=os.getcwd(),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (args, r.returncode, r.stderr[-2000:])
    return r

# Leg 1: seed the cache with one design point (its own journal).
run(["sweep", "--trace", trace_path, "--serve",
     "--journal", os.path.join(tmp, "j1"), "--db", db,
     "--sweep", "dram/latency=90"])

# Leg 2: fresh process serves 2 tickets — one cache hit, one simulated
# — with the metrics exposition on.
out2 = os.path.join(tmp, "serve2.json")
run(["sweep", "--trace", trace_path, "--serve",
     "--journal", os.path.join(tmp, "j2"), "--db", db,
     "--metrics-path", metrics, "-o", out2,
     "--sweep", "dram/latency=90,120"])

parsed = parse_exposition(open(metrics).read())   # must PARSE
assert parsed["ticket_latency_s_count"] == [({}, 2.0)], \
    parsed.get("ticket_latency_s_count")
assert parsed["cache_hits_total"] == [({}, 1.0)], \
    parsed.get("cache_hits_total")
assert parsed["variants_served_total"] == [({}, 2.0)]
states = {l["state"]: v for l, v in parsed["tickets_in_state"]}
assert states.get("done") == 2.0, states

res = json.load(open(out2))
assert res["variants"] == 2 and res["variants_per_sec"] > 0, res
assert res["p99_first_result_s"] and res["p99_first_result_s"] > 0
assert res["cache_hit_ratio"] == 0.5, res["cache_hit_ratio"]

# Streaming evidence in the journal: the simulated ticket's
# first_result record precedes every done record.
from graphite_tpu.sweep.service import read_journal
recs = read_journal(os.path.join(tmp, "j2"))
fr = [r["seq"] for r in recs if r["event"] == "first_result"]
dn = [r["seq"] for r in recs if r["event"] == "done"]
assert fr and dn and min(fr) < min(dn), (fr, dn)

# `status` subcommand folds the journal (no trace needed).
st = run(["status", "--journal", os.path.join(tmp, "j2"), "--json"])
sj = json.loads(st.stdout)
assert sj["counts"]["done"] == 2 and sj["open"] == 0, sj["counts"]
assert sj["p99_first_result_s"] is not None

# results_db ingest + latency regression flag: re-ingest the same row
# with a 10x p99 and expect the REGRESSION line.
sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
import results_db
rdb = results_db.open_db(os.path.join(tmp, "reg.db"))
base_row = {"p99_first_result_s": res["p99_first_result_s"],
            "cache_hit_ratio": res["cache_hit_ratio"],
            "variants": res["variants"],
            "host_seconds": res["host_seconds"]}
assert results_db.check_regression(rdb, "svc", base_row) is None
results_db.add_run(rdb, "svc", base_row)
slow = dict(base_row)
slow["p99_first_result_s"] = base_row["p99_first_result_s"] * 10
warn = results_db.check_regression(rdb, "svc", slow)
assert warn and "p99-first-result-s" in warn, warn
shutil.rmtree(tmp)
print("SERVICE OBSERVABILITY SMOKE OK (2 tickets: 1 simulated + 1 "
      "cache hit; exposition parsed, first_result precedes done, "
      "latency regression flag fires)")
PYEOF
)
obs_rc=$?
echo "$obs_out" | tail -3
if [ $obs_rc -ne 0 ]; then
    echo "SERVICE OBSERVABILITY GATE FAILED"
    fail=1
fi

# Streamed-ingest smoke gate (ISSUE 20 CI satellite): the SAME trace
# through the CLI twice — whole-trace vs --segment-events — in two
# subprocesses.  The streamed report must cross >= 4 segment seams with
# the footprint capped at two segments, agree with the whole-trace run
# on every aggregate counter and the completion time (the full
# every-SimState-leaf identity gate lives in tests/test_ingest.py),
# and export ingest.* spans beside the host spans in the Chrome trace.
# Then the results_db stall-fraction regression flag must fire on a
# doctored grown value (and the peak-bytes structural flag on growth).
ingest_out=$(timeout 1800 python - <<'PYEOF' 2>&1
import json, os, shutil, subprocess, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from graphite_tpu.events import synth

tmp = tempfile.mkdtemp()
trace_path = os.path.join(tmp, "long.npz")
synth.gen_radix(2, keys_per_tile=160, radix=16, seed=3).save(trace_path)

BASE = [sys.executable, "-c",
        "from graphite_tpu.cli import main; raise SystemExit(main())",
        "--general/total_cores=2"]

def run_cli(tag, extra):
    d = os.path.join(tmp, tag)
    os.makedirs(d, exist_ok=True)
    r = subprocess.run(
        BASE + ["run", "--trace", trace_path, "--telemetry-dir", d,
                "-o", os.path.join(d, "sim.out")] + extra,
        capture_output=True, text=True, timeout=900, cwd=os.getcwd())
    assert r.returncode == 0, (tag, r.returncode, r.stderr[-2000:])
    report = json.load(open(os.path.join(d, "run_report.json")))
    chrome = json.load(open(os.path.join(d, "run_trace.json")))
    return report, chrome

whole, _ = run_cli("whole", [])
streamed, chrome = run_cli("seg", ["--segment-events", "256"])

ing = streamed.get("ingest")
assert ing, "streamed report carries no ingest section"
assert ing["seams"] >= 4, ing
assert ing["num_segments"] >= 3, ing
assert ing["peak_device_trace_bytes"] == 2 * 2 * 256 * (8 + 3 * 4), ing
assert ing["ingest_stall_fraction"] >= 0.0
assert "ingest" not in whole, "whole-trace report grew an ingest section"

# Whole-trace agreement on the simulated numbers (counter aggregates +
# completion time) — the smoke tier of the bit-identity contract.
assert streamed["completion_time_ps"] == whole["completion_time_ps"], \
    (streamed["completion_time_ps"], whole["completion_time_ps"])
assert streamed["counters"] == whole["counters"]
assert streamed["quanta"] == whole["quanta"]

# Ingest spans render beside the host spans in the Chrome export.
names = {e.get("name", "") for e in chrome["traceEvents"]
         if e.get("ph") == "X" and e.get("pid") == 1}
assert any(n.startswith("ingest.") for n in names), sorted(names)

# results_db: the stall-fraction chain flags a >20% GROWTH, and the
# peak-bytes structural chain flags ANY growth.
sys.path.insert(0, os.path.join(os.getcwd(), "tools"))
import results_db
rdb = results_db.open_db(os.path.join(tmp, "reg.db"))
base_row = {"ingest_stall_fraction": max(
                ing["ingest_stall_fraction"], 0.004),
            "peak_device_trace_bytes": ing["peak_device_trace_bytes"],
            "host_seconds": streamed["host_seconds"]}
assert results_db.check_regression(rdb, "streamed", base_row) is None
results_db.add_run(rdb, "streamed", base_row)
grown = dict(base_row)
grown["ingest_stall_fraction"] = base_row["ingest_stall_fraction"] * 2
warn = results_db.check_regression(rdb, "streamed", grown)
assert warn and "ingest-stall-fraction" in warn, warn
fat = dict(base_row)
fat["peak_device_trace_bytes"] = base_row["peak_device_trace_bytes"] * 2
warn = results_db.check_regression(rdb, "streamed", fat)
assert warn and "peak_device_trace_bytes" in warn, warn
shutil.rmtree(tmp)
print("STREAMED INGEST SMOKE OK (%d seams, %d segments, counters + "
      "completion identical to whole-trace, stall/footprint "
      "regression flags fire)" % (ing["seams"], ing["num_segments"]))
PYEOF
)
ingest_rc=$?
echo "$ingest_out" | tail -3
if [ $ingest_rc -ne 0 ]; then
    echo "STREAMED INGEST GATE FAILED"
    fail=1
fi

if [ $fail -eq 0 ]; then
    echo "ALL MODULES PASSED"
else
    echo "FAILURES PRESENT"
fi
exit $fail
