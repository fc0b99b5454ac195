"""MOSI protocol tests (pr_l1_pr_l2_dram_directory_mosi).

The O state's contract (reference:
pr_l1_pr_l2_dram_directory_mosi/dram_directory_cntlr.cc): a reader hitting
an M entry downgrades the owner to O — the owner KEEPS its dirty copy and
forwards data to this and every later reader without any DRAM traffic;
dirty data reaches DRAM only when the owner finally evicts the line.
These tests pin that contract against the MSI baseline, plus the directory
invariants under O entries.
"""

import pytest
import numpy as np

from graphite_tpu.config import load_config
from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine.sim import Simulator, run_simulation
from graphite_tpu.engine.state import (dir_word_view, dword_owner,
                                       dword_state)
from graphite_tpu.events.schema import TraceBuilder
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

MOSI = "pr_l1_pr_l2_dram_directory_mosi"
MSI = "pr_l1_pr_l2_dram_directory_msi"



def _dir_words(sim):
    """[A, F] packed directory words of a finished simulation."""
    return dir_word_view(sim.state.dir_word,
                         sim.params.directory.associativity)

def make_params(tiles=4, protocol=MOSI, **over):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("caching_protocol/type", protocol)
    for k, v in over.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


def counters_np(summary):
    return {k: v for k, v in summary.counters.items()}


def _producer_reader_trace(readers=2):
    """Tile 0 dirties a line; tiles 1..readers read it in sequence."""
    tb = TraceBuilder(1 + readers)
    addr = synth.SHARED_BASE
    tb.write(0, addr, 8)
    for r in range(1, readers + 1):
        tb.stall_until(r, 5_000_000 * r)
        tb.read(r, addr, 8)
    return tb.build()


@pytest.mark.slow   # compile-heavy: tier-1 runs -m 'not slow'
def test_owner_forwards_without_dram():
    """SH on M: MOSI forwards from the owner — no DRAM write, no DRAM
    read for this or any later reader; MSI writes back and re-reads."""
    trace = _producer_reader_trace(readers=2)
    s_mosi = run_simulation(make_params(3, MOSI), trace)
    s_msi = run_simulation(make_params(3, MSI), trace)
    cm, cs = counters_np(s_mosi), counters_np(s_msi)

    # Both see one EX + two SH requests.
    assert int(cm["dir_ex_req"].sum()) == 1
    assert int(cm["dir_sh_req"].sum()) == 2
    # MOSI: the only DRAM read is tile 0's cold EX fill; readers are fed
    # by the owner.  No writeback ever reaches DRAM (nothing evicts).
    assert int(cm["dram_reads"].sum()) == 1
    assert int(cm["dram_writes"].sum()) == 0
    assert int(cm["dir_forwards"].sum()) == 2
    # MSI: the first reader's WB_REQ writes through; the second reader's
    # SH_REQ is served from DRAM (entry back in S).
    assert int(cs["dram_writes"].sum()) == 1
    assert int(cs["dram_reads"].sum()) >= 2
    assert int(cs["dir_forwards"].sum()) == 0


def test_o_entry_state_and_owner_kept():
    params = make_params(3, MOSI)
    trace = _producer_reader_trace(readers=2)
    sim = Simulator(params, trace)
    sim.run()
    dstate = np.asarray(dword_state(_dir_words(sim)))
    downer = np.asarray(dword_owner(_dir_words(sim)))
    o_entries = dstate == cachemod.O
    assert o_entries.sum() == 1
    assert downer[o_entries][0] == 0          # tile 0 still owns the line
    from graphite_tpu.engine.state import dir_sharers_view
    dsharers = np.asarray(dir_sharers_view(
        sim.state, sim.params.directory.associativity))
    # owner + both readers all in the sharer bitmap
    assert dsharers[o_entries][0, 0] == np.uint64(0b111)
    # the owner's own L2 copy is in O (downgraded from M, not S/I)
    l2_states = np.asarray(cachemod.meta_state(sim.state.l2.meta))[:, 0, :]
    assert (l2_states == cachemod.O).sum() == 1


def test_write_after_o_flushes_owner_and_sharers():
    """EX on an O entry: flush the owner, invalidate the other sharers,
    new writer becomes M owner — still no DRAM data traffic."""
    params = make_params(4, MOSI)
    tb = TraceBuilder(4)
    addr = synth.SHARED_BASE
    tb.write(0, addr, 8)                  # 0: M
    tb.stall_until(1, 5_000_000)
    tb.read(1, addr, 8)                   # 0 downgrades to O, forwards
    tb.stall_until(2, 10_000_000)
    tb.write(2, addr, 8)                  # EX on O: flush 0, inv 1
    tb.stall_until(0, 15_000_000)
    tb.read(0, addr, 8)                   # old owner must re-miss
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_forwards"].sum()) == 3   # SH fwd, EX flush fwd, final SH fwd
    assert int(c["dir_invalidations"].sum()) == 1   # reader 1 invalidated
    assert int(c["dram_writes"].sum()) == 0
    # tile 0's post-flush read missed (copy was flushed to I)
    assert int(c["l1d_read_miss"][0]) == 1
    dstate = np.asarray(dword_state(_dir_words(sim)))
    downer = np.asarray(dword_owner(_dir_words(sim)))
    o_entries = dstate == cachemod.O
    assert o_entries.sum() == 1
    assert downer[o_entries][0] == 2      # final owner is the last writer


def test_owner_upgrade_in_place():
    """The owner of an O entry re-writing its line upgrades O->M by
    invalidating the other sharers; its cache must hold ONE copy in M."""
    params = make_params(3, MOSI)
    tb = TraceBuilder(3)
    addr = synth.SHARED_BASE
    tb.write(0, addr, 8)                  # 0: M
    tb.stall_until(1, 5_000_000)
    tb.read(1, addr, 8)                   # 0 -> O
    tb.stall_until(0, 10_000_000)
    tb.write(0, addr, 8)                  # owner upgrades O -> M
    trace = tb.build()
    sim = Simulator(params, trace)
    s = sim.run()
    c = counters_np(s)
    assert int(c["dir_invalidations"].sum()) == 1
    dstate = np.asarray(dword_state(_dir_words(sim)))
    downer = np.asarray(dword_owner(_dir_words(sim)))
    m_entries = dstate == cachemod.M
    assert m_entries.sum() == 1
    assert downer[m_entries][0] == 0
    # exactly one copy of the line in tile 0's L2, in state M
    line = np.int32(addr >> 6)
    l2_tags = np.asarray(sim.state.l2.tags)[:, 0, :]
    l2_states = np.asarray(cachemod.meta_state(sim.state.l2.meta))[:, 0, :]
    hits = (l2_tags == line) & (l2_states != cachemod.I)
    assert hits.sum() == 1
    assert l2_states[hits][0] == cachemod.M


def test_mosi_invariants_under_contention():
    """Migratory + shared-reader mix: directory invariants hold at the end
    (single owner per M/O entry; every M entry's owner bitmap consistent)."""
    params = make_params(8, MOSI)
    trace = synth.gen_migratory(8, lines=6, rounds=3)
    sim = Simulator(params, trace)
    s = sim.run()
    assert s.to_dict()["all_done"]
    dstate = np.asarray(dword_state(_dir_words(sim)))
    downer = np.asarray(dword_owner(_dir_words(sim)))
    # M and O entries always carry a live owner
    assert np.all(downer[dstate == cachemod.M] >= 0)
    assert np.all(downer[dstate == cachemod.O] >= 0)
    # S/I entries never carry an owner
    assert np.all(downer[dstate == cachemod.S] == -1)
    assert np.all(downer[dstate == cachemod.I] == -1)


def test_mosi_saves_dram_traffic_vs_msi():
    """On a sharing-heavy workload MOSI's owner forwards must cut DRAM
    traffic relative to MSI (request counts drift slightly — different
    cache contents evolve different miss patterns — but every MOSI forward
    is a DRAM access MSI would have made)."""
    trace = synth.gen_radix(8, keys_per_tile=64, radix=16)
    c1 = counters_np(run_simulation(make_params(8, MOSI), trace))
    c2 = counters_np(run_simulation(make_params(8, MSI), trace))
    assert int(c1["dir_forwards"].sum()) > 0
    dram1 = int(c1["dram_reads"].sum() + c1["dram_writes"].sum())
    dram2 = int(c2["dram_reads"].sum() + c2["dram_writes"].sum())
    assert dram1 < dram2


def test_mosi_deterministic():
    params = make_params(4, MOSI)
    trace = synth.gen_migratory(4, lines=4, rounds=2)
    s1 = run_simulation(params, trace)
    s2 = run_simulation(params, trace)
    assert s1.completion_time_ps == s2.completion_time_ps
    c1, c2 = counters_np(s1), counters_np(s2)
    for k in c1:
        assert np.array_equal(c1[k], c2[k]), k
