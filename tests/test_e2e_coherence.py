"""End-to-end engine tests: coherence protocol timing + sync semantics.

Property/invariant style tests over full simulations — the oracle upgrade
over the reference's print-PASSED regression suite (SURVEY.md section 4):
the reference's shared_mem_test* / spawn / many_mutex / ping_pong apps
checked only functional completion; here we assert directory state, counter
identities, and ordering/serialization timing laws.
"""

import numpy as np
import pytest

from graphite_tpu.config import load_config
from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine.sim import DeadlockError, Simulator, run_simulation
from graphite_tpu.events.schema import TraceBuilder
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams


def make_params(tiles=8, **over):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    for k, v in over.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


PARAMS8 = make_params(8)


def counters_np(summary):
    return {k: v for k, v in summary.counters.items()}


def test_private_mem_completes():
    trace = synth.gen_private_mem(8, accesses=40, working_set_kb=4)
    s = run_simulation(PARAMS8, trace)
    assert s.to_dict()["all_done"]
    c = counters_np(s)
    assert int(c["l1d_read"].sum() + c["l1d_write"].sum()) == 8 * 40
    # every L2 miss reached a directory slice
    assert int(c["l2_miss"].sum()) == int(
        c["dir_sh_req"].sum() + c["dir_ex_req"].sum())
    # private data: no invalidations, no owner writebacks
    assert int(c["dir_invalidations"].sum()) == 0
    assert int(c["dir_writebacks"].sum()) == 0
    assert s.completion_time_ps > 0


def test_shared_readers_sharer_bitmap():
    trace = synth.gen_shared_readers(8, lines=8, passes=2)
    sim = Simulator(PARAMS8, trace)
    s = sim.run()
    c = counters_np(s)
    # each tile cold-misses each line exactly once; second pass hits
    assert int(c["l2_miss"].sum()) == 8 * 8
    assert int(c["dir_sh_req"].sum()) == 8 * 8
    assert int(c["dir_invalidations"].sum()) == 0
    # the directory must now record all 8 tiles as sharers of each line
    from graphite_tpu.engine.state import dir_word_view, dword_state
    dstate = np.asarray(dword_state(dir_word_view(
        sim.state.dir_word, sim.params.directory.associativity)))  # [A, F]
    from graphite_tpu.engine.state import dir_sharers_view
    dsharers = np.asarray(dir_sharers_view(
        sim.state, sim.params.directory.associativity))
    shared_entries = dstate == cachemod.S
    assert shared_entries.sum() == 8  # 8 lines tracked, one entry each
    bits = dsharers[shared_entries]
    assert np.all(bits[:, 0] == np.uint64(0xFF))


def test_producer_consumer_writeback():
    params = make_params(4)
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.write(0, addr, 8)            # tile 0 takes M
    tb.read(0, addr, 8)             # still M, local hit
    tb.stall_until(1, 5_000_000)
    tb.read(1, addr, 8)             # SH_REQ -> WB_REQ to owner 0, both S
    tb.stall_until(0, 10_000_000)
    tb.read(0, addr, 8)             # downgraded to S -> still a local hit
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_writebacks"].sum()) == 1
    assert int(c["dram_writes"].sum()) == 1
    # tile 0: one write miss, zero read misses (M hit, then S hit)
    assert int(c["l1d_write_miss"][0]) == 1
    assert int(c["l1d_read_miss"][0]) == 0
    assert int(c["l1d_read_miss"][1]) == 1


def test_write_invalidates_sharers():
    params = make_params(4)
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.read(0, addr, 8)
    tb.read(1, addr, 8)
    tb.stall_until(2, 5_000_000)
    tb.write(2, addr, 8)            # EX_REQ: invalidate sharers {0, 1}
    tb.stall_until(0, 10_000_000)
    tb.read(0, addr, 8)             # must miss again (copy invalidated)
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_invalidations"].sum()) == 2
    assert int(c["l1d_read_miss"][0]) == 2   # cold miss + post-inv miss
    # tile 0's final read downgraded writer 2's M entry: S, sharers {0, 2},
    # one owner writeback
    assert int(c["dir_writebacks"].sum()) == 1
    from graphite_tpu.engine.state import dir_word_view, dword_state
    dstate = np.asarray(dword_state(dir_word_view(
        sim.state.dir_word, sim.params.directory.associativity)))  # [A, F]
    from graphite_tpu.engine.state import dir_sharers_view
    dsharers = np.asarray(dir_sharers_view(
        sim.state, sim.params.directory.associativity))
    s_entries = dstate == cachemod.S
    assert s_entries.sum() == 1
    assert dsharers[s_entries][0, 0] == np.uint64(0b101)


def test_migratory_flush_chain():
    trace = synth.gen_migratory(4, lines=4, rounds=3)
    params = make_params(4)
    s = run_simulation(params, trace)
    c = counters_np(s)
    # each tile's write EX_REQ after another tile's M copy forces a flush
    # (owner leg) or an invalidation — the chain must be non-trivial
    assert int(c["dir_writebacks"].sum() + c["dir_invalidations"].sum()) > 0
    assert s.to_dict()["all_done"]


def test_ping_pong_ordering():
    params = make_params(4)
    trace = synth.gen_ping_pong(4, messages=8)
    s = run_simulation(params, trace)
    c = counters_np(s)
    assert int(c["sends"].sum()) == 4 * 8 * 2 // 2
    assert int(c["recvs"].sum()) == int(c["sends"].sum())
    assert s.to_dict()["all_done"]


def test_barrier_release_timing():
    params = make_params(4)
    tb = TraceBuilder(4)
    stalls = [1_000_000, 2_000_000, 3_000_000, 9_000_000]
    for t in range(4):
        tb.stall_until(t, stalls[t])
        tb.barrier(t, 0, 4)
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    # everyone released at >= the latest arrival
    assert int(np.min(s.clock)) >= max(stalls)
    assert s.to_dict()["all_done"]
    c = counters_np(s)
    assert int(c["barriers"].sum()) == 4


def test_barrier_reuse_across_phases():
    params = make_params(4)
    trace = synth.gen_barrier_compute(4, phases=3, max_cost=200)
    s = run_simulation(params, trace)
    assert s.to_dict()["all_done"]
    assert int(counters_np(s)["barriers"].sum()) == 12


def test_mutex_serialization():
    params = make_params(4)
    n_acq, crit = 4, 100
    trace = synth.gen_lock_contention(4, acquisitions=n_acq,
                                      critical_cycles=crit)
    s = run_simulation(params, trace)
    c = counters_np(s)
    assert int(c["mutex_acquires"].sum()) == 4 * n_acq
    # critical sections serialize: completion >= total critical work
    assert s.completion_time_ps >= 4 * n_acq * crit * 1000
    assert s.to_dict()["all_done"]


def test_mismatched_barrier_deadlocks():
    params = make_params(4)
    tb = TraceBuilder(4)
    for t in range(4):
        tb.barrier(t, 0, 5)   # 5 participants never arrive
    trace = tb.build()
    sim = Simulator(params, trace)
    with pytest.raises(DeadlockError):
        sim.run()


def test_radix_end_to_end():
    params = make_params(8)
    trace = synth.gen_radix(8, keys_per_tile=64, radix=16)
    s = run_simulation(params, trace)
    assert s.to_dict()["all_done"]
    c = counters_np(s)
    assert int(c["barriers"].sum()) == 3 * 8
    # the shared histogram/permutation phases force coherence traffic
    assert int(c["dir_ex_req"].sum()) > 0
    assert int(c["dir_invalidations"].sum() + c["dir_writebacks"].sum()) > 0


def test_deterministic():
    params = make_params(4)
    trace = synth.gen_migratory(4, lines=4, rounds=2)
    s1 = run_simulation(params, trace)
    s2 = run_simulation(params, trace)
    assert s1.completion_time_ps == s2.completion_time_ps
    c1, c2 = counters_np(s1), counters_np(s2)
    for k in c1:
        assert np.array_equal(c1[k], c2[k]), k
