"""What a later PR adds as files, found by the name in the data: a
configuration's own plain reference (benchmarks/references/), a traffic
mix's own generator (benchmarks/generators/), and per-layer readers that
see the window's counters and per-phase device time.  On the CPU at tiny
sizes, with the harness pointed at temporary directories."""

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
import phases  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from test_perfbench_files import check_traffic_file  # noqa: E402
from test_perfbench_run import SEED, assert_exact, radix8  # noqa: E402

SUBCLASSED = '''
import reference


class ReferenceSim(reference.ReferenceSim):
    """The default reference, through a subclass of its own."""
'''

SHIFTED = '''
import reference


class ReferenceSim(reference.ReferenceSim):
    """The default reference with tile 0's clock 1 ps late."""

    def digest(self):
        d = super().digest()
        d["clock"][0] += 1
        return d
'''

NO_CLASS = '''
def run(stop_quanta=None):
    return {}
'''

GENERATOR = '''
import traffic


def generate(tiles, seed, params):
    return traffic.gen_radix(tiles, seed, params["keys_per_tile"],
                             params["radix"])
'''

FILE_TRAFFIC = {"name": "radix_file", "generator": "radix_from_file",
                "source": "https://github.com/mit-carbon/Graphite/tree/"
                          "master/tests/benchmarks/radix",
                "params": {"keys_per_tile": 64, "radix": 16},
                "window": "restart"}

PHASE_READERS = ("window_walk_ms_per_round", "complex_slot_ms_per_round",
                 "resolve_memory_ms_per_round", "resolve_sync_ms_per_round",
                 "quantum_ms_per_round", "unscoped_ms_per_round")


@pytest.fixture
def plugin_dirs(tmp_path, monkeypatch):
    refs, gens = tmp_path / "references", tmp_path / "generators"
    refs.mkdir()
    gens.mkdir()
    for name, text in (("subclassed", SUBCLASSED), ("shifted", SHIFTED),
                       ("no_class", NO_CLASS)):
        (refs / f"{name}.py").write_text(text)
    (gens / "radix_from_file.py").write_text(GENERATOR)
    monkeypatch.setattr(core, "REFERENCE_DIR", str(refs))
    monkeypatch.setattr(traffic, "GENERATOR_DIR", str(gens))
    return refs, gens


@pytest.fixture(scope="module")
def window():
    """One restart window of the radix8 cell, run once for the reference
    tests."""
    c = radix8()
    out, sims, events = core.measure_program(
        c, SEED, 0.5, False, time.perf_counter(), check_chip=False)
    return out, sims, events, c


def with_reference(c, name):
    c = dict(c, config=copy.deepcopy(c["config"]))
    c["config"]["reference"] = name
    return c


def compared(out):
    return {k: v["value"] for k, v in out["compared"].items()}


def test_no_reference_key_compares_as_before(window):
    out, sims, events, c = window
    assert "reference" not in c["config"]
    assert core.reference_class(c["config"]) is reference.ReferenceSim
    got = core.finish(copy.deepcopy(out), sims, events, c)
    assert_exact(got)
    failed, want = core.check(sims, core.reference_model(c["config"]),
                              events, reference.ReferenceSim)
    assert failed == 0 and compared(got) == want


def test_named_reference_and_generator_run_through_measure(plugin_dirs,
                                                           monkeypatch):
    """A configuration naming its own reference (a subclass of the default
    one) under a traffic mix whose generator is a file: correct."""
    c = with_reference(radix8(), "subclassed")
    c["traffic"] = copy.deepcopy(FILE_TRAFFIC)
    used = []
    orig = core.check

    def spy(sims, model, events, ref_cls):
        used.append(ref_cls)
        return orig(sims, model, events, ref_cls)

    monkeypatch.setattr(core, "check", spy)
    out = core.measure(c, SEED, 0.5, False, time.perf_counter(),
                       check_chip=False)
    assert_exact(out)
    assert out["compared"]["simulations_compared"]["value"] >= 1
    (cls,) = used
    assert cls is not reference.ReferenceSim
    assert issubclass(cls, reference.ReferenceSim)
    assert cls.__module__ == "bench_reference_subclassed"


def test_named_reference_one_ps_off_is_refused(window, plugin_dirs):
    out, sims, events, c = window
    got = core.finish(copy.deepcopy(out), sims, events,
                      with_reference(c, "shifted"))
    assert got["correct"] is False
    assert got["failed"] == len(sims)
    assert got["compared"]["max_clock_gap_ps"]["value"] == 1
    assert got["compared"]["values_differing"]["value"] >= 1


@pytest.mark.parametrize("name, why", [
    ("absent", "no reference module"),
    ("no_class", "has no ReferenceSim"),
    ("../reference", "no reference module")])
def test_a_named_reference_that_is_not_there_is_an_error(name, why,
                                                         plugin_dirs):
    """Never a fallback to the default reference: the run stops before
    set-up, naming the path it looked at."""
    refs, _ = plugin_dirs
    c = with_reference(radix8(), name)
    with pytest.raises(core.HarnessError, match=why) as err:
        core.measure(c, SEED, 0.5, False, time.perf_counter(),
                     check_chip=False)
    assert str(refs) in str(err.value)


def test_generator_file_is_found_and_accepted(plugin_dirs):
    _, gens = plugin_dirs
    assert traffic.generators() == {"radix", "fft", "radix_from_file"}
    check_traffic_file(FILE_TRAFFIC, "radix_file")
    c = radix8()
    c["traffic"] = copy.deepcopy(FILE_TRAFFIC)
    ev = core.make_events(c, SEED)
    want = traffic.gen_radix(8, SEED, 64, 16)
    for f in ("ops", "addr", "arg", "arg2"):
        assert np.array_equal(ev[f], want[f])
    missing = dict(FILE_TRAFFIC, generator="absent")
    with pytest.raises(FileNotFoundError, match="absent.py") as err:
        traffic.generate(missing, 8, SEED)
    assert str(gens) in str(err.value)


def test_traced_run_gives_readers_counters_and_phases(monkeypatch):
    """The readers' ctx carries the window's counters and round counters;
    with no device plane on the CPU, the phase and span readers find
    nothing and stay silent."""
    seen = []
    orig = core.load_reader

    def spy(name):
        read = orig(name)

        def recorded(ctx):
            seen.append(ctx)
            return read(ctx)
        return recorded

    monkeypatch.setattr(core, "load_reader", spy)
    out = core.measure(radix8(), SEED, 0.5, True, time.perf_counter(),
                       check_chip=False)
    assert_exact(out)
    ctx = seen[0]
    assert ctx["counters"]["icount"] == out["window"]["icount"] > 0
    assert ctx["round_ctrs"]["round_ctr"] == out["window"]["rounds"] > 0
    assert set(ctx["round_ctrs"]) == set(core.ROUND_FIELDS)
    assert ctx["phase_s"] == {} and ctx["span_s"] == {}
    for name in PHASE_READERS + ("dispatch_ms",):
        assert name not in out["metrics"]
    assert out["metrics"]["rounds_per_kinst"]["value"] > 0
    assert out["window"]["trace"]["hlo_s"] > 0


SPLIT = {"engine.barrier": 0.01, "engine.schedule": 0.002,
         "engine.sample": 0.003, "engine.window_walk": 1.2,
         "engine.complex_slot": 0.15, "engine.fast_forward": 0.05,
         "engine.resolve_memory": 0.7, "engine.resolve_sync": 0.004,
         "unscoped": 0.1}
CTX = {"rounds": 2000, "phase_s": SPLIT,
       "span_s": {"sim.dispatch": [0.006, 0.008], "sim.sync": [0.4]}}


@pytest.mark.parametrize("name, want", [
    ("window_walk_ms_per_round", 0.625), ("complex_slot_ms_per_round", 0.075),
    ("resolve_memory_ms_per_round", 0.35),
    ("resolve_sync_ms_per_round", 0.002), ("quantum_ms_per_round", 0.0075),
    ("unscoped_ms_per_round", 0.05), ("dispatch_ms", 7.0)])
def test_phase_and_span_readers(name, want):
    bm = core.benchmark()
    entry = {m["name"]: m for m in bm["per_layer"]}[name]
    assert entry["moves"] == "sim_mips" and "workloads" not in entry
    read = core.load_reader(name)
    assert read(CTX) == pytest.approx(want)
    for empty in ({"rounds": 2000, "phase_s": {}, "span_s": {}},
                  {"rounds": 0, "phase_s": {}, "span_s": {}}, {}):
        assert read(empty) is None


def test_six_phase_readers_cover_the_split():
    """Together the six device phases are the whole split: every scope of
    phases.PHASES and the unscoped ops, once each."""
    total = sum(core.load_reader(n)(CTX) for n in PHASE_READERS)
    assert total * CTX["rounds"] / 1e3 == pytest.approx(sum(SPLIT.values()))
    assert set(SPLIT) == set(phases.PHASES) | {phases.UNSCOPED}
