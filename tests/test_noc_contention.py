"""emesh_hop_by_hop link contention tests.

Pin the contended-mesh contract (reference:
network_model_emesh_hop_by_hop.cc:146 + per-link queue models): each hop
probes the link's configured queue model (``history_list`` /
``history_tree``: a ring of busy intervals, so a later batch's earlier
packet fits an idle gap; ``basic``: one carried horizon, so it waits);
same-link packets of one batch serialize in FCFS order; an idle mesh
reproduces the zero-load hop-counter latency exactly.
"""

import dataclasses

import pytest
import jax.numpy as jnp
import numpy as np

from graphite_tpu.config import load_config
from graphite_tpu.engine import noc, noc_flight
from graphite_tpu.engine.sim import run_simulation
from graphite_tpu.events.schema import TraceBuilder
from graphite_tpu.events import synth
from graphite_tpu.params import NetworkParams, SimParams

NET = NetworkParams(model="emesh_hop_by_hop", flit_width_bits=64,
                    router_delay_cycles=1, link_delay_cycles=1,
                    queue_model_enabled=True, queue_model_type="history_tree",
                    broadcast_tree_enabled=False, queue_slots=100)


def make_params(tiles=16, **over):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("network/memory", "emesh_hop_by_hop")
    for k, v in over.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


QTYPES = ("basic", "history_list", "history_tree")


def _fly(src, dst, depart, flits, mesh_w, mesh_h, T, rings=None,
         active=None, net=NET):
    src = jnp.asarray(src, jnp.int32)
    K = src.shape[0]
    if rings is None:
        rings = noc_flight.make_link_rings(net, T)
    if active is None:
        active = jnp.ones(K, dtype=bool)
    return noc_flight.flight(
        net, mesh_w, mesh_h, src, jnp.asarray(dst, jnp.int32),
        jnp.asarray(depart, jnp.int64), flits, active, rings,
        jnp.full(K, 500, jnp.int32))   # 2 GHz -> 500 ps/cycle


def test_idle_mesh_matches_zero_load():
    """A single packet on an idle mesh pays exactly the hop-counter
    latency: hops*(router+link) + (flits-1), in network cycles."""
    # 4x4 mesh: tile 0 -> tile 15 is 3+3 = 6 hops.
    r = _fly([0], [15], [0], 5, 4, 4, 16)
    zero_load = noc.unicast_ps(
        NET, jnp.asarray([0]), jnp.asarray([15]),
        (5 * 64) // 8 - noc.PACKET_HEADER_BYTES,   # payload giving 5 flits
        jnp.asarray([500], jnp.int32), 4)
    assert int(r.arrival[0]) == 6 * 2 * 500 + 4 * 500
    assert int(r.arrival[0]) == int(zero_load[0])
    assert int(r.wait_ps[0]) == 0


def test_hotspot_serializes_fcfs():
    """Hand-computed case: three 1-flit packets from tile 1 region all
    crossing the SAME last link (tile 1 -> tile 0) serialize by arrival.

    2x2 mesh, packets from tile 1 to tile 0 departing at t=0, 0, 0:
    link (W, tile 1) serves them one flit apart; hop latency 2 cycles.
    Arrivals: 2c, 3c, 4c (c = 500 ps cycle).
    """
    r = _fly([1, 1, 1], [0, 0, 0], [0, 0, 0], 1, 2, 2, 4)
    arr = sorted(int(a) for a in np.asarray(r.arrival))
    c = 500
    assert arr == [2 * c, 3 * c, 4 * c]
    # exactly 0 + 1 + 2 flit-times of queueing were accumulated
    assert int(np.asarray(r.wait_ps).sum()) == (0 + 1 + 2) * c


def test_carried_horizon_backpressures_next_batch():
    """Link horizons persist: a second batch arriving while the link is
    still busy from batch one waits for it (the queue model's memory)."""
    r1 = _fly([1], [0], [0], 8, 2, 2, 4)            # 8-flit occupancy
    r2 = _fly([1], [0], [0], 8, 2, 2, 4, rings=r1.rings)
    assert int(r2.wait_ps[0]) == 8 * 500            # waits out batch 1
    assert int(r2.arrival[0]) == int(r1.arrival[0]) + 8 * 500


@pytest.mark.parametrize("qtype, wait", [
    ("basic", 14000), ("history_list", 0), ("history_tree", 0)])
def test_later_batch_earlier_packet(qtype, wait):
    """Batch one holds link (W, tile 1) over [10000, 14000); batch two's
    one-flit packet reaches it at 0.  A history queue fits it into the idle
    gap before the recorded interval; the basic model's carried horizon
    makes it wait behind the later packet (the phantom convoy)."""
    net = dataclasses.replace(NET, queue_model_type=qtype)
    r1 = _fly([1], [0], [10000], 8, 2, 2, 4, net=net)
    assert int(r1.wait_ps[0]) == 0
    r2 = _fly([1], [0], [0], 1, 2, 2, 4, rings=r1.rings, net=net)
    assert int(r2.wait_ps[0]) == wait
    assert int(r2.arrival[0]) == wait + 2 * 500


@pytest.mark.parametrize("qtype", QTYPES)
def test_idle_mesh_is_zero_load_for_every_type(qtype):
    """Packets whose paths share no link, and one that stays home, pay
    exactly the hop-counter latency under every link queue model."""
    net = dataclasses.replace(NET, queue_model_type=qtype)
    src, dst = [0, 4, 8, 15, 5], [3, 7, 11, 12, 5]     # rows E, E, E, W
    r = _fly(src, dst, [0, 100, 2000, 0, 7], 3, 4, 4, 16, net=net)
    want = np.asarray([0, 100, 2000, 0, 7]) + np.asarray(noc.unicast_ps(
        net, jnp.asarray(src), jnp.asarray(dst),
        (3 * 64) // 8 - noc.PACKET_HEADER_BYTES,   # payload giving 3 flits
        jnp.full(5, 500, jnp.int32), 4))
    assert np.asarray(r.arrival).tolist() == want.tolist()
    assert not np.asarray(r.wait_ps).any()


def test_ring_wraps():
    """A link's ring keeps its ``queue_slots`` latest intervals: the third
    interval overwrites the first, which then no longer delays a packet
    arriving inside it."""
    net = dataclasses.replace(NET, queue_model_type="history_list",
                              queue_slots=2)
    link = noc_flight.DIR_W * 4 + 1                  # (W, tile 1)
    rings = None
    for dep in (0, 10000, 20000):                    # 8 flits: 4000 ps busy
        r = _fly([1], [0], [dep], 8, 2, 2, 4, rings=rings, net=net)
        rings = r.rings
    assert int(rings.ptr[link]) == 3
    assert np.asarray(rings.start[:, link]).tolist() == [20000, 10000]
    assert np.asarray(rings.end[:, link]).tolist() == [24000, 14000]
    late = _fly([1], [0], [1000], 1, 2, 2, 4, rings=rings, net=net)
    assert int(late.wait_ps[0]) == 0
    kept = _fly([1], [0], [11000], 1, 2, 2, 4, rings=rings, net=net)
    assert int(kept.wait_ps[0]) == 3000


def test_uncontended_networks_carry_no_link_queues():
    """Without its queue model, or off the hop-by-hop mesh, a network has
    zero-sized link rings: the hop-counter programs carry no new bytes."""
    off = dataclasses.replace(NET, queue_model_enabled=False)
    counter = dataclasses.replace(NET, model="emesh_hop_counter")
    for net in (off, counter):
        assert not noc_flight.contended(net)
        rings = noc_flight.make_link_rings(net, 64)
        assert all(a.size == 0 for a in rings)
    rings = noc_flight.make_link_rings(NET, 64)
    assert rings.start.shape == (100, 4 * 64)


@pytest.mark.parametrize("qtype, over, slots", [
    ("history_tree", None, 100), ("history_list", None, 100),
    ("history_tree", 7, 7), ("basic", 7, 1)])
def test_link_rings_take_the_configured_list_size(qtype, over, slots):
    """Each link's ring holds [queue_model/<type>] max_list_size busy
    intervals (100 in carbon_sim.cfg); basic keeps one horizon."""
    sets = {"network/emesh_hop_by_hop/queue_model/type": qtype}
    if over is not None:
        sets[f"queue_model/{qtype}/max_list_size"] = over
    net = make_params(16, **sets).net_memory
    assert net.queue_slots == slots
    assert noc_flight.make_link_rings(net, 16).start.shape == (slots, 64)


def test_distinct_links_no_interference():
    """Packets on disjoint paths never wait for each other."""
    #  4x4 mesh: 0->1 (E link of 0) and 5->6 (E link of 5)
    r = _fly([0, 5], [1, 6], [0, 0], 4, 4, 4, 16)
    assert int(np.asarray(r.wait_ps).sum()) == 0


@pytest.mark.slow   # compile-heavy: tier-1 runs -m 'not slow'
def test_e2e_contended_slower_than_zero_load():
    """BASELINE config-5 shape: all tiles hammer lines homed at one tile;
    the contended model must charge visibly more time than hop-counter."""
    tiles = 16
    tb_args = dict(lines=12, passes=2)
    trace = synth.gen_shared_readers(tiles, **tb_args)
    p_cont = make_params(tiles)
    p_zero = make_params(tiles, **{"network/memory": "emesh_hop_counter"})
    s_cont = run_simulation(p_cont, trace)
    s_zero = run_simulation(p_zero, trace)
    wait = int(s_cont.counters["net_link_wait_ps"].sum())
    assert wait > 0
    assert s_cont.completion_time_ps > s_zero.completion_time_ps
    # zero-load run records no link contention
    assert int(s_zero.counters["net_link_wait_ps"].sum()) == 0


def test_contended_run_deterministic():
    params = make_params(8)
    trace = synth.gen_migratory(8, lines=4, rounds=2)
    s1 = run_simulation(params, trace)
    s2 = run_simulation(params, trace)
    assert s1.completion_time_ps == s2.completion_time_ps
    assert int(s1.counters["net_link_wait_ps"].sum()) \
        == int(s2.counters["net_link_wait_ps"].sum())
