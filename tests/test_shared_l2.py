"""Shared-distributed-L2 protocol tests (pr_l1_sh_l2_msi / _mesi).

The slice contract (reference: pr_l1_sh_l2_msi/l2_cache_cntlr.cc with the
directory integrated in the L2 slice; MESI variant pr_l1_sh_l2_mesi/):
every tile hosts an L2 slice; an L1 miss goes to the line's home slice;
data comes from the slice (or an L1 owner) on a slice hit — DRAM is read
only on a slice miss and written only on a dirty slice eviction.  MESI
grants E to a sole first reader, whose later store upgrades silently with
NO second home request.
"""

import numpy as np

from graphite_tpu.config import load_config
from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine.sim import Simulator, run_simulation
from graphite_tpu.engine.state import dir_word_view, dword_owner, dword_state
from graphite_tpu.events.schema import TraceBuilder
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

SH_MSI = "pr_l1_sh_l2_msi"
SH_MESI = "pr_l1_sh_l2_mesi"



def _dir_words(sim):
    """[A, F] packed directory words of a finished simulation."""
    return dir_word_view(sim.state.dir_word,
                         sim.params.directory.associativity)

def make_params(tiles=4, protocol=SH_MSI, **over):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("caching_protocol/type", protocol)
    for k, v in over.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


def counters_np(summary):
    return {k: v for k, v in summary.counters.items()}


def test_slice_hit_skips_dram():
    """Second reader of a line hits the home slice: exactly ONE DRAM read
    total (private MSI would read DRAM again for the second SH_REQ)."""
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.read(0, addr, 8)
    tb.stall_until(1, 5_000_000)
    tb.read(1, addr, 8)
    trace = tb.build()
    s = run_simulation(make_params(4, SH_MSI), trace)
    c = counters_np(s)
    assert int(c["dram_reads"].sum()) == 1
    assert int(c["l2_access"].sum()) == 2     # both requests hit the slice
    assert int(c["l2_miss"].sum()) == 1       # only the first missed
    assert int(c["dram_writes"].sum()) == 0


def test_mesi_silent_upgrade_no_second_request():
    """MESI: sole reader gets E; its later store upgrades locally —
    dir_ex_req stays 0.  MSI: the same store must send an EX_REQ."""
    tb = TraceBuilder(2)
    addr = synth.SHARED_BASE
    tb.read(0, addr, 8)
    tb.compute(0, 10, 5)
    tb.write(0, addr, 8)
    trace = tb.build()
    c_mesi = counters_np(run_simulation(make_params(2, SH_MESI), trace))
    c_msi = counters_np(run_simulation(make_params(2, SH_MSI), trace))
    assert int(c_mesi["dir_ex_req"].sum()) == 0
    assert int(c_mesi["l1d_write_miss"].sum()) == 0
    assert int(c_msi["dir_ex_req"].sum()) == 1
    assert int(c_msi["l1d_write_miss"].sum()) == 1


def test_mesi_second_reader_downgrades_owner():
    """E owner must be reachable: a second reader triggers the owner leg
    (the owner may have silently upgraded E->M, so the flushed data is
    conservatively slice-dirty: entry -> O), both end as sharers."""
    params = make_params(4, SH_MESI)
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.read(0, addr, 8)               # 0 gets E
    tb.stall_until(1, 5_000_000)
    tb.read(1, addr, 8)               # owner leg to 0; entry -> O {0, 1}
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_writebacks"].sum()) == 1
    dstate = np.asarray(dword_state(_dir_words(sim)))
    from graphite_tpu.engine.state import dir_sharers_view
    dsharers = np.asarray(dir_sharers_view(
        sim.state, sim.params.directory.associativity))
    o_entries = dstate == cachemod.O
    assert o_entries.sum() == 1
    assert dsharers[o_entries][0, 0] == np.uint64(0b11)


def test_write_invalidates_sharers_shared_l2():
    params = make_params(4, SH_MSI)
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.read(0, addr, 8)
    tb.read(1, addr, 8)
    tb.stall_until(2, 5_000_000)
    tb.write(2, addr, 8)              # invalidate sharers {0, 1}
    tb.stall_until(0, 10_000_000)
    tb.read(0, addr, 8)               # must re-miss in L1
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_invalidations"].sum()) == 2
    assert int(c["l1d_read_miss"][0]) == 2
    # final read downgraded writer 2's M entry -> slice-dirty O
    dstate = np.asarray(dword_state(_dir_words(sim)))
    o_entries = dstate == cachemod.O
    assert o_entries.sum() == 1
    downer = np.asarray(dword_owner(_dir_words(sim)))
    assert downer[o_entries][0] == -1          # dirty at slice, no L1 owner
    # no DRAM data traffic beyond the cold fill
    assert int(c["dram_reads"].sum()) == 1
    assert int(c["dram_writes"].sum()) == 0


def test_dirty_l1_eviction_flushes_to_slice():
    """Forcing a dirty L1D victim: the slice entry becomes O (dirty at
    slice), and a later reader is served from the slice — still no DRAM
    traffic after the cold fills."""
    params = make_params(4, SH_MSI)
    nsets = params.l1d.num_sets
    assoc = params.l1d.associativity
    line = params.line_size
    tb = TraceBuilder(4)
    base = synth.SHARED_BASE
    # assoc+1 writes mapping to the same L1D set: the first line becomes
    # the (dirty) victim of the last fill.
    for k in range(assoc + 1):
        tb.write(0, base + k * nsets * line, 8)
    tb.stall_until(1, 5_000_000)
    tb.read(1, base, 8)               # served by the slice's O copy
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    # reader's request found slice-dirty data: no owner leg, no extra DRAM
    assert int(c["dram_reads"].sum()) == assoc + 1   # cold fills only
    assert int(c["dram_writes"].sum()) == 0
    assert int(c["dir_writebacks"].sum()) == 0       # no owner flush legs
    dstate = np.asarray(dword_state(_dir_words(sim)))
    # base line's entry: O with sharer {1} after the read
    assert (dstate == cachemod.O).sum() >= 1


def test_sh_l2_invariants_under_contention():
    for proto in (SH_MSI, SH_MESI):
        params = make_params(8, protocol=proto)
        trace = synth.gen_migratory(8, lines=6, rounds=3)
        sim = Simulator(params, trace)
        s = sim.run()
        assert s.to_dict()["all_done"], proto
        dstate = np.asarray(dword_state(_dir_words(sim)))
        downer = np.asarray(dword_owner(_dir_words(sim)))
        # M/E entries carry exactly one live L1 owner; S/O/I never do
        assert np.all(downer[dstate == cachemod.M] >= 0), proto
        assert np.all(downer[dstate == cachemod.E] >= 0), proto
        assert np.all(downer[dstate == cachemod.S] == -1), proto
        assert np.all(downer[dstate == cachemod.O] == -1), proto
        c = counters_np(s)
        # slice accounting holds: every slice miss read DRAM
        assert int(c["l2_miss"].sum()) == int(c["dram_reads"].sum()), proto


def test_sh_l2_deterministic():
    params = make_params(4, SH_MESI)
    trace = synth.gen_migratory(4, lines=4, rounds=2)
    s1 = run_simulation(params, trace)
    s2 = run_simulation(params, trace)
    assert s1.completion_time_ps == s2.completion_time_ps
    c1, c2 = counters_np(s1), counters_np(s2)
    for k in c1:
        assert np.array_equal(c1[k], c2[k]), k
