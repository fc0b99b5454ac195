"""bench.py and tools/weak_scaling.py on the CPU at tiny sizes: a bench
row keeps its metrics off the TPU, and a weak-scaling leg (the path of
bench's radix1024_shard8 row) runs to its row on the virtual devices."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench  # noqa: E402
import weak_scaling  # noqa: E402
from graphite_tpu.events import synth  # noqa: E402


def test_bench_run_row_on_cpu(monkeypatch):
    monkeypatch.setattr(bench, "TELEMETRY_DIR", "")
    row = bench._run(lambda T: synth.gen_radix(T, keys_per_tile=8, radix=8),
                     8, label="tiny")
    assert row["kind"] == "completed" and row["all_done"]
    assert row["engine_rounds"] > 0 and row["mips"] > 0
    assert row["hbm_utilization_vs_peak"] is None      # no peak off the TPU


def test_hbm_peak_unknown_kind_is_an_error():
    assert bench.hbm_peak_gbps("TPU v5 lite") == 819.0
    with pytest.raises(ValueError, match="no HBM peak"):
        bench.hbm_peak_gbps("TPU v99")


def test_shard_ab_row_on_virtual_devices(monkeypatch):
    monkeypatch.setattr(weak_scaling, "KEYS_PER_TILE", 4)
    monkeypatch.setattr(weak_scaling, "RADIX", 8)
    row = weak_scaling.shard_ab_row(2, tiles=16, quanta=2, warm=2)
    assert row["kind"] == "completed" and row["sharded_matches_single"]
    assert row["timed_quanta"] > 0


def test_measure_resident_leg():
    row = weak_scaling._measure_resident(2, 16, quanta=2, warm=1)
    assert row["kind"] == "completed" and row["timed_quanta"] > 0
    assert row["modeled_step_bytes_moved"] > 0
    assert row["modeled_step_bytes_moved_replicated"] > 0
