"""Host-side simulation driver + end-of-run summary.

Driver: the role of the reference's Simulator singleton + sim-thread
manager (reference: common/system/simulator.cc:83-203) collapses to a small
host loop launching fused device steps (engine/quantum.py) and polling
termination — there are no server threads to start or join.

Summary: the reference aggregates every component's outputSummary() into
one ``sim.out`` on process 0 (reference: simulator.cc:135-170,
tile_manager_summary.cc); here the counters already live in device arrays,
so the summary is one device->host transfer + formatting.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import numpy as np

from graphite_tpu.config import Config
from graphite_tpu.engine.quantum import megarun, megastep  # noqa: F401
# (megastep stays exported: the sharded mesh path, the multi-host dryrun,
# and __graft_entry__ drive it directly)
from graphite_tpu.engine.state import SimState, TraceArrays, make_state
from graphite_tpu.events.schema import Trace
from graphite_tpu.params import SimParams
from graphite_tpu.time_base import ps_to_ns


class SimSummary:
    """Counter roll-up with sim.out-style rendering."""

    def __init__(self, params: SimParams, state: SimState,
                 host_seconds: float, steps: int,
                 ingest_stats: Optional[Dict] = None):
        self.params = params
        self.host_seconds = host_seconds
        self.steps = steps
        # Streaming-ingest accounting (engine/ingest.py stats dict);
        # None for whole-trace runs.
        self.ingest_stats = ingest_stats
        self.quanta = int(state.ctr_quantum)
        self.clock = np.asarray(state.clock)
        # Per-STREAM done (== per-tile when the scheduler is off).  A
        # seat only shows its currently-scheduled stream, so under the
        # ThreadScheduler the store's flags are patched with the seated
        # streams' live values — the summary reports EVERY stream's
        # completion, not one all-streams scalar (VERDICT weak #9: the
        # old reduction hid which stream was stuck).
        if state.sched_enabled:
            self.done = np.asarray(
                state.strm_done.at[state.seat_stream].set(state.done))
        else:
            self.done = np.asarray(state.done)
        self.period_ps = np.asarray(state.period_ps)
        self.stat_filled = int(state.stat_filled)
        self.stat_time = np.asarray(state.stat_time)
        self.stat_scalars = np.asarray(state.stat_scalars)
        self.stat_icount = np.asarray(state.stat_icount)
        self.counters: Dict[str, np.ndarray] = {
            f: np.asarray(getattr(state.counters, f))
            for f in state.counters._fields
        }
        # Round-12 adaptive-fidelity attribution: engaged analytic
        # rounds, quanta with >= 1 fast-forwarded span, events priced
        # in closed form (all zero when tpu/fast_forward = 0).
        self.ff_rounds = int(state.ctr_ff)
        self.ff_quanta = int(state.ctr_ffq)
        self.ff_events = int(state.ff_events)
        self.vm_brk = int(state.vm_brk)
        self.vm_mmap_bytes = int(state.vm_mmap_bytes)
        self.vm_munmap_bytes = int(state.vm_munmap_bytes)
        self.tel_gauges = np.asarray(state.tel_gauges)
        self.tel_cursor = np.asarray(state.tel_cursor)
        self.tel_pend = np.asarray(state.tel_pend)

    # ------------------------------------------------------------ metrics

    @property
    def completion_time_ps(self) -> int:
        return int(self.clock.max())

    @property
    def total_instructions(self) -> int:
        return int(self.counters["icount"].sum())

    @property
    def simulated_mips(self) -> float:
        if self.host_seconds <= 0:
            return float("inf")
        return self.total_instructions / self.host_seconds / 1e6

    STAT_SERIES = ("icount", "net_mem_flits", "net_user_flits",
                   "dram_reads", "dram_writes", "live_l2_lines",
                   "sharer_copies", "net_link_wait_ps")

    @property
    def _stats_filled(self) -> int:
        """Samples recorded into the stat_scalars series ring — 0 when
        only telemetry sampled (tel_* arrays have their own series; the
        stat_scalars ring is a 1-column dummy then)."""
        from graphite_tpu.engine.state import stats_ring_enabled
        return self.stat_filled if stats_ring_enabled(self.params) else 0

    def power_trace(self) -> Dict[str, np.ndarray]:
        """Per-interval power from the sampled energy counters — the
        reference's [runtime_energy_modeling/power_trace] file
        (carbon_sim.cfg:141-145, TileEnergyMonitor)."""
        from graphite_tpu.energy import power_trace
        return power_trace(self.params, self.stat_time, self.stat_scalars,
                           self._stats_filled)

    def write_power_trace(self, path: str) -> None:
        pt = self.power_trace()
        with open(path, "w") as f:
            f.write("time_ns,dynamic_w,leakage_w,total_w\n")
            for i in range(len(pt["time_ns"])):
                f.write(f"{pt['time_ns'][i]:.1f},{pt['dynamic_w'][i]:.6f},"
                        f"{pt['leakage_w'][i]:.6f},{pt['total_w'][i]:.6f}\n")

    def stats_trace(self) -> Dict[str, np.ndarray]:
        """Periodic samples taken at quantum boundaries (the reference's
        StatisticsManager trace files + progress trace, as arrays).
        Cumulative series; differentiate for rates."""
        n = self._stats_filled
        out = {"time_ps": self.stat_time[:n]}
        for i, name in enumerate(self.STAT_SERIES):
            out[name] = self.stat_scalars[i, :n]
        if self.params.progress_enabled:
            out["tile_icount"] = self.stat_icount[:n]
        return out

    def write_stats_csv(self, path: str) -> None:
        """Statistics-trace file (reference: statistics_manager.cc trace
        file output, one row per sample)."""
        tr = self.stats_trace()
        cols = [k for k in tr if k != "tile_icount"]
        with open(path, "w") as f:
            f.write(",".join(cols) + "\n")
            for i in range(len(tr["time_ps"])):
                f.write(",".join(str(int(tr[c][i])) for c in cols) + "\n")

    def write_progress_trace(self, path: str) -> None:
        """Per-tile progress CSV (reference: pin/progress_trace.cc —
        (time, instruction count) rows per tile)."""
        if not self.params.progress_enabled:
            raise ValueError(
                "progress trace was not recorded: set "
                "progress_trace/enabled=true before the run")
        tr = self.stats_trace()
        with open(path, "w") as f:
            f.write("time_ps," + ",".join(
                f"tile{t}" for t in range(self.params.num_tiles)) + "\n")
            for i in range(len(tr["time_ps"])):
                row = tr["tile_icount"][i]
                f.write(str(int(tr["time_ps"][i])) + ","
                        + ",".join(str(int(v)) for v in row) + "\n")

    # -------------------------------------------------------- telemetry
    # ([telemetry] engine-health round metrics; graphite_tpu/obs)

    def telemetry_trace(self) -> Optional[Dict[str, np.ndarray]]:
        """Sampled engine-health gauge series (obs/metrics.TEL_SERIES
        rows over the shared quantum-boundary sample ring); None when
        [telemetry] was disabled for the run."""
        if not self.params.telemetry_enabled:
            return None
        from graphite_tpu.obs.metrics import TEL_SERIES
        n = self.stat_filled
        out = {"time_ps": self.stat_time[:n]}
        for i, name in enumerate(TEL_SERIES):
            out[name] = self.tel_gauges[i, :n]
        return out

    def tel_cursor_trace(self) -> Optional[np.ndarray]:
        """[samples, T] per-tile trace-cursor snapshots (per-tile
        progress in events); None when telemetry was disabled."""
        if not self.params.telemetry_enabled:
            return None
        return self.tel_cursor[:self.stat_filled]

    def tel_pend_trace(self) -> Optional[np.ndarray]:
        """[samples, T] per-tile pend_kind snapshots (occupancy / stall
        attribution); None when telemetry was disabled."""
        if not self.params.telemetry_enabled:
            return None
        return self.tel_pend[:self.stat_filled]

    def run_report(self, tracer=None, workload: Optional[str] = None,
                   extra: Optional[Dict] = None) -> Dict:
        """Machine-readable RunReport dict (obs/export.build_run_report):
        the JSON superset of render(), plus host spans and the sampled
        telemetry series."""
        from graphite_tpu.obs.export import build_run_report
        return build_run_report(self, tracer=tracer, workload=workload,
                                extra=extra)

    def write_telemetry(self, dirpath: str, tracer=None,
                        workload: Optional[str] = None,
                        prefix: str = "run") -> Dict[str, str]:
        """Write the RunReport + Chrome trace-event JSON artifacts."""
        from graphite_tpu.obs.export import write_telemetry_dir
        return write_telemetry_dir(dirpath, self, tracer=tracer,
                                   workload=workload, prefix=prefix)

    def energy(self):
        """Analytic McPAT/DSENT-shaped energy breakdown (graphite_tpu.
        energy) on the final counters at each module's current V/f."""
        from graphite_tpu.energy import compute_energy
        return compute_energy(self.params, self.counters,
                              self.completion_time_ps, self.period_ps)

    def to_dict(self) -> Dict:
        agg = {k: int(v.sum()) for k, v in self.counters.items()}
        out = {
            "num_tiles": self.params.num_tiles,
            "completion_time_ns": ps_to_ns(self.completion_time_ps),
            "host_seconds": self.host_seconds,
            "device_steps": self.steps,
            "quanta": self.quanta,
            "total_instructions": self.total_instructions,
            "simulated_mips": self.simulated_mips,
            "all_done": bool(self.done.all()),
            # Per-stream completion (VERDICT weak #9): how many of the
            # trace's streams retired DONE — with the ThreadScheduler
            # this counts descheduled streams too, so a stuck run shows
            # WHICH fraction finished instead of one false/true.
            "streams_done": int(self.done.sum()),
            "num_streams": int(self.done.shape[0]),
            "aggregate": agg,
        }
        if self.params.fast_forward > 0:
            out["ff_rounds"] = self.ff_rounds
            out["ff_quanta"] = self.ff_quanta
            out["ff_events"] = self.ff_events
            out["ff_quanta_frac"] = round(
                self.ff_quanta / max(self.quanta, 1), 4)
        if self.params.enable_power_modeling:
            out["energy"] = self.energy().to_dict()
        vm_sec = self.vm_summary()
        if vm_sec is not None:
            out["vm"] = vm_sec
        ing = self.ingest_section()
        if ing is not None:
            out["ingest"] = ing
        return out

    def ingest_section(self) -> Optional[Dict]:
        """Streaming-ingest roll-up (None for whole-trace runs): the
        engine/ingest.py stats plus the stall FRACTION of this run's
        host wall clock — the bench's keeps-up metric (near zero when
        the prefetch hid every seam)."""
        if self.ingest_stats is None:
            return None
        out = dict(self.ingest_stats)
        out["ingest_stall_fraction"] = round(
            out["ingest_stall_seconds"] / self.host_seconds, 6) \
            if self.host_seconds > 0 else 0.0
        return out

    def vm_summary(self):
        """Simulated address-space accounting (engine/vm.summarize;
        reference vm_manager.cc segments) — None when the trace made no
        memory-management syscalls."""
        from graphite_tpu.engine import vm as vmmod
        return vmmod.summarize(
            self.params.num_tiles, self.params.stack_base,
            self.params.stack_size_per_core, self.vm_brk,
            self.vm_mmap_bytes, self.vm_munmap_bytes)

    def render(self) -> str:
        c = self.counters
        agg = {k: v.sum() for k, v in c.items()}
        lines = []
        w = 46
        def row(k, v):
            lines.append(f"    {k:<{w}}: {v}")
        lines.append("[general]")
        row("Total Tiles", self.params.num_tiles)
        row("Completion Time (in ns)", f"{ps_to_ns(self.completion_time_ps):.1f}")
        row("Streams Completed",
            f"{int(self.done.sum())} / {int(self.done.shape[0])}")
        row("Total Instructions", agg["icount"])
        row("Host Time (in s)", f"{self.host_seconds:.3f}")
        row("Simulated MIPS", f"{self.simulated_mips:.3f}")
        if self.params.fast_forward > 0:
            lines.append("[fast_forward]")
            row("Analytic Rounds", self.ff_rounds)
            row("Fast-Forwarded Quanta",
                f"{self.ff_quanta} / {self.quanta}")
            row("Events Priced In Closed Form", self.ff_events)
        lines.append("[core]")
        row("Total Instructions", agg["icount"])
        row("Branches", agg["branches"])
        row("Branch Mispredictions", agg["mispredicts"])
        lines.append("[l1_icache]")
        row("Cache Accesses", agg["l1i_access"])
        row("Cache Misses", agg["l1i_miss"])
        lines.append("[l1_dcache]")
        row("Read Accesses", agg["l1d_read"])
        row("Read Misses", agg["l1d_read_miss"])
        row("Write Accesses", agg["l1d_write"])
        row("Write Misses", agg["l1d_write_miss"])
        lines.append("[l2_cache]")
        row("Cache Accesses", agg["l2_access"])
        row("Cache Misses", agg["l2_miss"])
        if self.params.track_miss_types:
            row("Cold Misses", agg["l2_miss_cold"])
            row("Capacity Misses", agg["l2_miss_capacity"])
            row("Sharing Misses", agg["l2_miss_sharing"])
        lines.append("[dram_directory]")
        row("Shared Requests", agg["dir_sh_req"])
        row("Exclusive Requests", agg["dir_ex_req"])
        row("Invalidations", agg["dir_invalidations"])
        row("Writebacks", agg["dir_writebacks"])
        row("Cache-to-Cache Forwards", agg["dir_forwards"])
        row("Evictions", agg["dir_evictions"])
        row("Conflict-Round Deferrals", agg["dir_deferrals"])
        lines.append("[dram]")
        row("Reads", agg["dram_reads"])
        row("Writes", agg["dram_writes"])
        lines.append("[network (memory)]")
        row("Packets", agg["net_mem_pkts"])
        row("Flits", agg["net_mem_flits"])
        row("Link Contention Delay (in ns, total)",
            f"{ps_to_ns(agg['net_link_wait_ps']):.1f}")
        lines.append("[network (user)]")
        row("Packets", agg["net_user_pkts"])
        row("Flits", agg["net_user_flits"])
        lines.append("[sync]")
        row("Barriers", agg["barriers"])
        row("Mutex Acquires", agg["mutex_acquires"])
        row("Cond Waits", agg["cond_waits"])
        row("Cond Signals/Broadcasts", agg["cond_signals"])
        row("Messages Sent", agg["sends"])
        row("Messages Received", agg["recvs"])
        lines.append("[threads]")
        row("Spawns", agg["spawns"])
        row("Joins", agg["joins"])
        lines.append("[syscalls]")
        row("Syscalls", agg["syscalls"])
        row("Syscall Time (in ns, total)",
            f"{ps_to_ns(agg['syscall_ps']):.1f}")
        vm_sec = self.vm_summary()
        if vm_sec is not None:
            lines.append("[vm]")
            row("Data Segment (brk) Bytes", vm_sec["data_segment_bytes"])
            row("Dynamic Segment (mmap) Bytes", vm_sec["mmap_bytes"])
            row("Unmapped (munmap) Bytes", vm_sec["munmap_bytes"])
            row("Stack Segment Bytes", vm_sec["stack_segment_bytes"])
            if vm_sec["brk_overflow"] or vm_sec["dynamic_overflow"]:
                row("SEGMENT OVERFLOW", ", ".join(
                    name for name, flag
                    in (("brk", vm_sec["brk_overflow"]),
                        ("dynamic", vm_sec["dynamic_overflow"])) if flag))
        lines.append("[stalls]")
        row("Memory Stall (in ns, total)", f"{ps_to_ns(agg['mem_stall_ps']):.1f}")
        row("Sync Stall (in ns, total)", f"{ps_to_ns(agg['sync_stall_ps']):.1f}")
        if self.params.enable_power_modeling:
            e = self.energy()
            seconds = max(self.completion_time_ps * 1e-12, 1e-30)
            lines.append("[energy]")
            for name in ("core", "l1i", "l1d", "l2", "directory", "dram",
                         "network", "leakage"):
                row(f"{name.capitalize()} Energy (in uJ)",
                    f"{float(getattr(e, name).sum()) * 1e6:.3f}")
            row("Total Energy (in uJ)", f"{float(e.total.sum()) * 1e6:.3f}")
            row("Average Power (in W)",
                f"{float(e.total.sum()) / seconds:.3f}")
            row("Tile Area (in mm^2)", f"{e.area_mm2_per_tile:.3f}")
        return "\n".join(lines) + "\n"


class DeadlockError(RuntimeError):
    """No tile made progress across a full polling window — the trace is
    waiting on something that can never happen (e.g. mismatched barrier
    participant counts)."""


class Simulator:
    """Headless simulator-as-library (the MODE= pattern of the reference's
    unit tests, tests/unit/shared_mem_basic/Makefile:6)."""

    def __init__(self, params: SimParams, trace: Trace):
        # More trace streams than tiles engages the ThreadScheduler
        # (round-robin multi-thread-per-core, reference
        # thread_scheduler.h:30-56); fewer is an error, as is exceeding
        # tiles x general/max_threads_per_core (checked in make_state).
        if trace.num_tiles < params.num_tiles:
            raise ValueError(
                f"trace has {trace.num_tiles} streams, params expect "
                f"at least {params.num_tiles}")
        from graphite_tpu.obs import span
        self.params = params
        # Streaming segmented ingest (round 16, trace/segment_events):
        # only two fixed-capacity segments are ever device-resident
        # (active + prefetch) and the host feeds the device across
        # megarun boundaries — traces larger than HBM simulate whole.
        # engine/ingest.py documents the bit-identity contract.
        self.ingest = None
        if params.segment_events > 0:
            from graphite_tpu.engine import ingest as ingest_mod
            self.ingest = ingest_mod.StreamingIngest(params, trace)
            self.trace = self.ingest.arrays
        else:
            with span("trace.device_upload", events=trace.ops.size):
                self.trace = TraceArrays.from_trace(trace)
        # CAPI channel state is O(T^2); only allocate it when the trace
        # actually messages (scan once, host-side).
        from graphite_tpu.isa import EventOp
        ops = np.asarray(trace.ops)
        has_capi = bool(((ops == int(EventOp.SEND))
                         | (ops == int(EventOp.RECV))).any())
        if has_capi and trace.num_tiles > params.num_tiles:
            raise ValueError(
                "CAPI SEND/RECV with multi-thread-per-core scheduling is "
                "not supported yet (channel state is tile-addressed)")
        with span("state.alloc", num_tiles=params.num_tiles):
            self.state = make_state(params, has_capi=has_capi,
                                    num_streams=trace.num_tiles)
        self.steps = 0
        self.host_seconds = 0.0
        # True when the last run() exited on an expired wall-clock
        # budget (state intact at a window boundary, checkpointable).
        self.preempted = False

    def run(self, max_steps: Optional[int] = None,
            poll_every: int = 8,
            budget_s: Optional[float] = None) -> SimSummary:
        """Run megasteps until every tile is DONE (or max_steps).

        ``budget_s``: wall-clock budget — on expiry the loop exits at
        the next window boundary with ``self.preempted`` True; a
        save_checkpoint / restore_checkpoint / run() sequence then
        continues bit-identically (resume determinism is the
        checkpoint module's contract)."""
        from graphite_tpu.log import get_logger
        from graphite_tpu.obs import span
        lg = get_logger("driver")
        lg.info("run: %d tiles, %d events/tile, protocol=%s",
                self.params.num_tiles, self.trace.num_events,
                self.params.protocol)
        self.preempted = False
        t0 = time.perf_counter()
        last_progress = None
        qps = self.params.quanta_per_step
        quanta = 0
        first_dispatch = True
        while True:
            # One device dispatch per polling window: megarun loops
            # quantum steps ON DEVICE and exits early once every stream
            # is done — the per-megastep dispatch round trips used to
            # dominate small-T wall clock.
            window = poll_every if max_steps is None \
                else max(min(poll_every, max_steps - self.steps), 0)
            if window == 0:
                break
            # The first window pays jit trace+compile (or cache load) on
            # top of device time; its span is named apart so compile
            # cost is attributable in the exported host track.
            with span("sim.compile+window" if first_dispatch
                      else "sim.window", quanta=window * qps):
                om_any = False
                if self.ingest is not None:
                    from graphite_tpu.engine import ingest as ingest_mod
                    # Dispatch is async; the prefetch's host slice +
                    # upload below overlaps the device compute — that
                    # overlap IS the double buffer.
                    self.state, om = ingest_mod.megarun(
                        self.params, self.state, self.trace, window * qps)
                    self.ingest.start_prefetch()
                    done, cursor_sum, clock_sum, quanta, om_any = \
                        jax.device_get(
                            (self.state.all_done(),
                             self.state.cursor.sum(),
                             self.state.clock.sum(),
                             self.state.ctr_quantum, om.any()))
                elif self.params.shard_state == "resident":
                    from graphite_tpu.engine import resident
                    self.state = resident.megarun(
                        self.params, self.state, self.trace, window * qps)
                else:
                    self.state = megarun(self.params, self.state,
                                         self.trace, window * qps)
                if self.ingest is None:
                    done, cursor_sum, clock_sum, quanta = jax.device_get(
                        (self.state.all_done(), self.state.cursor.sum(),
                         self.state.clock.sum(), self.state.ctr_quantum))
            first_dispatch = False
            if bool(om_any):
                # Segment seam: the megarun stopped at a quantum
                # boundary with some stream needing its next segment.
                om_np, cur_np = jax.device_get((om, self.state.cursor))
                self.trace = self.ingest.swap(om_np, cur_np)
            # Megastep-equivalent step count (reporting + max_steps
            # budget), from the quanta the device actually ran.
            self.steps = -(-int(quanta) // qps)
            if bool(done):
                break
            if max_steps is not None and self.steps >= max_steps:
                break
            if budget_s is not None \
                    and time.perf_counter() - t0 >= budget_s:
                self.preempted = True
                break
            # Segment swaps count as progress: a seam megarun may
            # commit zero quanta (the very first quantum needed data),
            # which is forward motion as long as bases advanced — the
            # ingest itself raises on a no-progress swap.
            base_sum = self.ingest.base_sum if self.ingest is not None \
                else 0
            progress = (int(cursor_sum), int(clock_sum), base_sum)
            if progress == last_progress:
                raise DeadlockError(
                    f"no progress after {self.steps} steps "
                    f"(cursor_sum={cursor_sum}, clock_sum={clock_sum})")
            last_progress = progress
        self.host_seconds = time.perf_counter() - t0
        # Quanta are exact; the megastep-equivalent count would bill a
        # partial early-exit window as a full megastep (ADVICE r5).
        lg.info("run finished: %d quanta (%d-quanta windows), %.2f host-s",
                int(quanta), qps, self.host_seconds)
        return self.summary()

    def summary(self) -> SimSummary:
        return SimSummary(self.params, self.state, self.host_seconds,
                          self.steps,
                          ingest_stats=self.ingest.stats()
                          if self.ingest is not None else None)

    # -------------------------------------------------- checkpoint/resume
    # (absent in the reference — SURVEY.md section 5.4; pure-array state
    # makes it a flatten+save here)

    def save_checkpoint(self, path: str) -> None:
        from graphite_tpu.engine.checkpoint import save_checkpoint
        # Streamed runs checkpoint at segment seams (run() only returns
        # at megarun boundaries, which every seam is): the ingest frame
        # rides beside the state so resume re-slices the same segments.
        ingest = None
        if self.ingest is not None:
            ingest = {"base": self.ingest.bases,
                      "segment_events": self.ingest.plan.segment_events,
                      "n_total": self.ingest.plan.n_total}
        save_checkpoint(path, self.state, self.steps, ingest=ingest)

    def restore_checkpoint(self, path: str) -> None:
        from graphite_tpu.engine.checkpoint import load_checkpoint
        self.state, self.steps = load_checkpoint(path, self.params)
        if self.ingest is not None:
            from graphite_tpu.engine.checkpoint import load_ingest
            frame = load_ingest(path)
            if frame is not None:
                if frame["n_total"] != self.ingest.plan.n_total:
                    raise ValueError(
                        f"streamed checkpoint was cut from a "
                        f"{frame['n_total']}-event trace; this trace "
                        f"has {self.ingest.plan.n_total}")
                bases = frame["base"]
            else:
                # Whole-trace (v26/v27 non-streamed) checkpoint into a
                # streamed run: derive bases from the restored cursors —
                # base placement never affects values, only which
                # columns are resident, so any base <= cursor (capped)
                # resumes bit-identically.
                bases = np.asarray(self.state.cursor)
            self.ingest.rebase(bases)
            self.trace = self.ingest.arrays
        if self.params.shard_state == "resident" \
                and self.params.tile_shards > 1:
            # Checkpoints are whole-array (the save seam gathers); a
            # resident run re-places its restored state tile-sharded.
            from graphite_tpu.parallel import mesh as meshmod
            mesh = meshmod.make_mesh(
                jax.devices()[:self.params.tile_shards])
            self.state = meshmod.resident_place(
                self.state, mesh, self.params.num_tiles)


def run_simulation(params: SimParams, trace: Trace,
                   max_steps: Optional[int] = None) -> SimSummary:
    return Simulator(params, trace).run(max_steps=max_steps)


def run_simulation_from_trace(cfg: Config, trace_path: str) -> SimSummary:
    """CLI entry (graphite_tpu.cli 'run')."""
    from graphite_tpu.obs import span
    with span("trace.load", path=trace_path):
        trace = Trace.load(trace_path)
    with span("params.resolve"):
        params = SimParams.from_config(cfg, num_tiles=trace.num_tiles)
    with span("sim.run", num_tiles=params.num_tiles):
        return run_simulation(params, trace)
