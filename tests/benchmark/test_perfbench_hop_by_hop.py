"""The contended mesh's configuration (``hbh1024``) and its plain
reference (``benchmarks/references/hop_by_hop.py``), on the CPU at tiny
sizes: the program equals the reference exactly under every link queue
model, a program that prices links by another model is refused, a
reference one ps off is refused, and with contention off the reference is
the default one."""

import copy
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))
sys.path.insert(0, HERE)

import core  # noqa: E402
import reference  # noqa: E402
from test_perfbench_run import SEED, assert_exact, radix8  # noqa: E402

QTYPES = ("basic", "history_list", "history_tree")


def contended(tiles, traffic, qtype):
    """The ``hbh1024.fft`` cell cut to ``tiles`` tiles, its links queued by
    ``qtype``, under a small FFT or radix trace in restart mode."""
    c = core.cell("hbh1024.fft")
    c["config"] = copy.deepcopy(c["config"])
    sets = c["config"]["sets"]
    sets["general/total_cores"] = tiles
    sets["network/emesh_hop_by_hop/queue_model/type"] = qtype
    c["config"]["model"]["noc"]["queue_model"] = qtype
    if traffic == "fft":
        c["traffic"] = dict(copy.deepcopy(c["traffic"]), window="restart",
                            params={"points_log2": 12 if tiles > 16 else 10})
    else:
        c["traffic"] = dict(copy.deepcopy(core.cell("tiles64.radix")
                                          ["traffic"]), scale={},
                            params={"total_keys": 32 * tiles, "radix": 16})
    return c


def with_queue_model(c, qtype):
    c = dict(c, config=copy.deepcopy(c["config"]))
    c["config"]["model"]["noc"]["queue_model"] = qtype
    return c


_WINDOWS = {}


def window(tiles, traffic, qtype):
    """One measured window of the program, run once per case."""
    key = (tiles, traffic, qtype)
    if key not in _WINDOWS:
        c = contended(tiles, traffic, qtype)
        out, sims, events = core.measure_program(
            c, SEED, 0.5, False, time.perf_counter(), check_chip=False)
        _WINDOWS[key] = (out, sims, events, c)
    return _WINDOWS[key]


CASES = [(16, "fft", "basic"), (16, "radix", "history_list"),
         (16, "fft", "history_tree"), (64, "radix", "basic"),
         (64, "fft", "history_list"), (64, "radix", "history_tree")]


@pytest.mark.parametrize("tiles, traffic, qtype", CASES,
                         ids=[f"{t}_{g}_{q}" for t, g, q in CASES])
def test_program_equals_reference(tiles, traffic, qtype):
    """Clocks, cursors and every counter, net_link_wait_ps among them, are
    the reference's exactly; the traffic really queues."""
    out, sims, events, c = window(tiles, traffic, qtype)
    got = core.finish(copy.deepcopy(out), sims, events, c)
    assert_exact(got)
    assert got["compared"]["simulations_compared"]["value"] >= 1
    assert sum(int(s["counters"]["net_link_wait_ps"].sum())
               for s in sims) > 0


@pytest.mark.parametrize("qtype, other", [
    ("basic", "history_tree"), ("history_tree", "basic"),
    ("history_list", "basic")])
def test_another_link_model_is_refused(qtype, other):
    """A program whose links run one queue model, against a reference of
    another: the carried horizon is refused by the history reference, and
    the other way round."""
    tiles, traffic = {"basic": (16, "fft"), "history_tree": (16, "fft"),
                      "history_list": (16, "radix")}[qtype]
    out, sims, events, c = window(tiles, traffic, qtype)
    got = core.finish(copy.deepcopy(out), sims, events,
                      with_queue_model(c, other))
    assert got["correct"] is False
    assert got["compared"]["values_differing"]["value"] > 0


def test_reference_one_ps_off_is_refused():
    """The reference with one packet's start at one link 1 ps late."""
    out, sims, events, c = window(16, "fft", "history_tree")
    base = core.reference_class(c["config"])

    class Shifted(base):
        def link_probe(self, link, arr, svc, rows):
            start = super().link_probe(link, arr, svc, rows)
            if not getattr(self, "shifted", False):
                self.shifted = True
                start = start.copy()
                start[0] += 1
            return start

    failed, got = core.check(sims, core.reference_model(c["config"]),
                             events, Shifted)
    assert failed == len(sims)
    assert got["values_differing"] >= 1
    assert got["max_clock_gap_ps"] >= 1


def test_contention_off_is_the_default_reference():
    """Without a link queue model the subclass's digest is the default
    reference's, value for value."""
    c = radix8()
    model = core.reference_model(c["config"])
    events = core.make_events(c, SEED)
    cls = core.reference_class(dict(c["config"], reference="hop_by_hop"))
    assert issubclass(cls, reference.ReferenceSim)
    assert "queue_model" not in model["noc"]
    a = cls(model, events).run()
    b = reference.ReferenceSim(model, events).run()
    assert a.keys() == b.keys()
    for k in a:
        if k == "counters":
            assert a[k].keys() == b[k].keys()
            for n in a[k]:
                np.testing.assert_array_equal(a[k][n], b[k][n])
        elif isinstance(a[k], np.ndarray):
            np.testing.assert_array_equal(a[k], b[k])
        else:
            assert a[k] == b[k], k


def test_hbh1024_is_tiles1024_on_the_contended_mesh():
    """The configuration differs from tiles1024 only in its memory network
    and the reference that models it."""
    hbh, base = core.cell("hbh1024.fft")["config"], \
        core.cell("tiles1024.fft")["config"]
    extra = {k: v for k, v in hbh["sets"].items()
             if base["sets"].get(k) != v}
    assert extra == {
        "network/memory": "emesh_hop_by_hop",
        "network/emesh_hop_by_hop/queue_model/enabled": True,
        "network/emesh_hop_by_hop/queue_model/type": "history_tree"}
    assert set(base["sets"]) <= set(hbh["sets"])
    for k in base["model"]:
        if k != "noc":
            assert hbh["model"][k] == base["model"][k], k
    assert {k: v for k, v in hbh["model"]["noc"].items()
            if k in base["model"]["noc"]} == base["model"]["noc"]
    params = core.program_params(hbh)
    net = params.net_memory
    assert (net.model, net.queue_model_enabled, net.queue_model_type,
            net.broadcast_tree_enabled, net.queue_slots) == (
        "emesh_hop_by_hop", True, "history_tree",
        hbh["model"]["noc"]["broadcast_tree"],
        hbh["model"]["noc"]["ring_slots"])
    assert net.queue_slots == 100     # carbon_sim.cfg's max_list_size
    assert hbh["reference"] == "hop_by_hop" and hbh["reduced"] == []
    assert core.cell("hbh1024.fft")["traffic"] == \
        core.cell("tiles1024.fft")["traffic"]


def test_reference_counts_what_the_ring_forgets():
    """``link_diag`` counts the packets that reach a link before the end
    of an interval its ring has overwritten: none where the ring holds
    the source's 100 intervals in this small window, some where it holds
    two."""
    c = contended(16, "fft", "history_tree")
    model = core.reference_model(c["config"])
    events = core.make_events(c, SEED)
    cls = core.reference_class(c["config"])
    full = cls(model, events)
    full.run()
    short = copy.deepcopy(model)
    short["noc"]["ring_slots"] = 2
    two = cls(short, events)
    two.run()
    assert full.link_diag["probes"] == two.link_diag["probes"] > 0
    assert full.link_diag["before_forgotten"] == 0
    assert two.link_diag["before_forgotten"] > 0


@pytest.mark.parametrize("split, want", [
    ({"engine.noc_flight": 1.5, "engine.resolve_memory": 0.2}, 0.75),
    ({"engine.resolve_memory": 0.2}, None), ({}, None)])
def test_noc_flight_reader(split, want):
    """ms of the flight loop per engine round; silent where the program
    traced no such scope (the parent of this scope, a hop-counter mesh)."""
    bm = core.benchmark()
    entry = {m["name"]: m for m in bm["per_layer"]}["noc_flight_ms_per_round"]
    assert entry["workloads"] == ["hbh1024.fft"]
    assert entry["moves"] == "sim_mips"
    read = core.load_reader("noc_flight_ms_per_round")
    got = read({"rounds": 2000, "phase_s": split})
    assert got == (None if want is None else pytest.approx(want))
    assert read({"rounds": 0, "phase_s": split}) is None
