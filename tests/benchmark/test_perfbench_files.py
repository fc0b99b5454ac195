"""The benchmark's data files: BENCHMARK.json, configurations, traffic
mixes, metric readers and the peaks table, checked without the chip."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))

import core  # noqa: E402
import peaks  # noqa: E402
import traffic  # noqa: E402

BM = core.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
URL = re.compile(r"https?://\S+")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert BM["command"][1] == "benchmarks/run.py"
    assert os.path.isfile(os.path.join(ROOT, BM["command"][1]))


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_use_allowed_characters(kind):
    names = [e["name"] for e in BM[kind]]
    assert len(set(names)) == len(names)
    for e in BM[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("config", "traffic"):
            if k in e:
                assert NAME.match(e[k])
        for r in e.get("reduced", []):
            assert NAME.match(r)


def test_metrics_are_wired():
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_loads_and_names_its_source(cfg):
    assert cfg["file"].startswith("benchmarks/")
    c = core.load_json(os.path.join(ROOT, cfg["file"]))
    assert URL.match(c["source"]) and c["source"] == cfg["source"]
    assert c["sets"]["tpu/miss_chain"] == 0
    assert c["sets"]["tpu/fast_forward"] == 0
    model = core.reference_model(c)
    assert model["engine"]["block_events"] == c["sets"]["tpu/block_events"]
    assert isinstance(c["reduced"], list) and isinstance(c["assumed"], list)
    # The program accepts the keys the file sets.
    params = core.program_params(c)
    assert params.num_tiles == c["sets"]["general/total_cores"]
    assert params.miss_chain == 0 and params.fast_forward == 0


def check_traffic_file(t, name):
    """A traffic file names itself, its public source, a generator that is
    built in or a file of benchmarks/generators/, and a window mode."""
    assert t["name"] == name and URL.search(t["source"])
    assert t["generator"] in traffic.generators()
    assert traffic.resolve(t, 64)["window"] in ("restart", "sustained")


@pytest.mark.parametrize("name", sorted({w["traffic"]
                                         for w in BM["workloads"]}))
def test_traffic_loads_and_names_its_source(name):
    t = core.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                    name + ".json"))
    check_traffic_file(t, name)


@pytest.mark.parametrize("wl", BM["workloads"], ids=lambda w: w["name"])
def test_trace_shape_does_not_depend_on_seed(wl):
    c = core.cell(wl["name"])
    a = core.make_events(c, 1)
    b = core.make_events(c, 2 ** 31 + 12345)
    for f in ("ops", "addr", "arg", "arg2"):
        assert a[f].shape == b[f].shape
    assert np.array_equal(a["ops"], b["ops"])
    assert not np.array_equal(a["addr"], b["addr"])
    # Every seed brings the same work in another order: the same
    # multiset of addresses.
    assert np.array_equal(np.sort(a["addr"], axis=None),
                          np.sort(b["addr"], axis=None))
    assert a["ops"].shape[0] == core.tiles_of(c["config"])
    assert wl["chips"] == 1


def test_generators_copy_the_program_generators():
    """traffic.py's copies equal events/synth.py's originals (FFT up to the
    seeded assignment of partitions to tiles)."""
    from graphite_tpu.events import synth
    keys = np.random.default_rng(5).integers(0, 16, size=(8, 64))
    ev = traffic.radix_trace(keys, 16)
    tr = synth.gen_radix(8, keys_per_tile=64, radix=16, seed=5)
    for f in ("ops", "addr", "arg", "arg2"):
        assert np.array_equal(ev[f], getattr(tr, f))
    ev = traffic.gen_fft(8, 9, 64)
    tr = synth.gen_fft(num_tiles=8, points_per_tile=64, writeback=True)
    perm = np.random.default_rng(9).permutation(8)
    for f in ("ops", "addr", "arg", "arg2"):
        assert np.array_equal(ev[f], getattr(tr, f)[perm])


def test_peaks_table():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9000")


def test_benchmark_json_is_small_and_well_formed():
    raw = open(os.path.join(ROOT, "BENCHMARK.json")).read()
    assert len(raw.encode()) <= 64 * 1024
    for w in BM["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BM["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    json.loads(raw)
