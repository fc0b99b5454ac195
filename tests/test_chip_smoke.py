"""chip_smoke.py's phases on the CPU, at the small sizes its fixture
records beside the chip shapes: the same phase code the chip run takes,
so tier 1 exercises the script itself.  The script's own entry point
refuses the CPU; the chip run is the builder's (CHANGES.md)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402


@pytest.fixture
def clock():
    with chip_smoke.CompileClock() as c:
        yield c


def test_goldens_phase(clock):
    chip_smoke.phase_goldens(clock)


def test_headline_phase_matches_fixture(clock):
    got = chip_smoke.phase_headline(clock, "radix8")
    assert got["all_done"] and got["engine_rounds"] > 0


def test_probe_phase_matches_fixture(clock):
    got = chip_smoke.phase_probe(clock, "probe16")
    assert not got["all_done"]          # bounded: a probe, not a run


def test_sharded_phase(clock):
    """The --chips 4 phase on 4 of the 8 virtual CPU devices, at the
    probe16 width and a 16-tile resident pair."""
    chip_smoke.phase_sharded(clock, shards=4, probe="probe16",
                             resident=dict(tiles=16, lines=4, rounds=2))


def test_fixture_covers_every_workload():
    with open(chip_smoke.FIXTURE) as f:
        fixture = json.load(f)
    assert set(fixture) == set(chip_smoke.HEADLINE) | set(chip_smoke.PROBE)
    assert fixture["radix64"]["all_done"]


def test_phase_mismatch_raises(clock, monkeypatch, tmp_path):
    """A digest that differs from the fixture fails the phase."""
    with open(chip_smoke.FIXTURE) as f:
        fixture = json.load(f)
    fixture["probe16"]["engine_rounds"] += 1
    bad = tmp_path / "fixture.json"
    bad.write_text(json.dumps(fixture))
    monkeypatch.setattr(chip_smoke, "FIXTURE", str(bad))
    with pytest.raises(chip_smoke.SmokeFailure, match="engine_rounds"):
        chip_smoke.phase_probe(clock, "probe16")


def test_refuses_cpu(capsys):
    """No TPU: non-zero exit, and no result line."""
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out
