"""The harness's runs on the CPU at tiny sizes: each window mode against
the plain reference, the per-layer readers in a traced run, the control
(the program's miss-chain path) and faults planted under the timed path,
all refused; and the refusals of a run without a chip or outside its
checkout."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import core  # noqa: E402

SEED = 2 ** 31 + 977


def tiny(name, tiles, params, scale=None):
    """A cell of BENCHMARK.json cut to a size a test run holds."""
    c = core.cell(name)
    c["config"] = copy.deepcopy(c["config"])
    c["config"]["sets"]["general/total_cores"] = tiles
    c["traffic"] = copy.deepcopy(c["traffic"])
    c["traffic"]["params"].update(params)
    c["traffic"]["scale"] = {str(tiles): scale} if scale else {}
    return c


def radix8():
    return tiny("tiles64.radix", 8, {"total_keys": 512, "radix": 16})


def run(c, seconds=0.5, traced=False):
    import time
    return core.measure(c, SEED, seconds, traced, time.perf_counter(),
                        check_chip=False)


def assert_exact(out):
    assert out["correct"] is True, out
    assert out["failed"] == 0
    for k, lim in core.LIMITS.items():
        assert out["compared"][k]["value"] <= lim
    assert list(out)[-1] == "compared"


def test_restart_window_matches_reference():
    out = run(radix8())
    assert_exact(out)
    assert out["attempted"] >= 1
    assert out["compared"]["simulations_compared"]["value"] == \
        out["attempted"]
    assert out["metrics"]["sim_mips"]["value"] > 0
    assert out["metrics"]["setup_s"]["unit"] == "s"


def test_sustained_window_cut_matches_reference():
    c = tiny("tiles1024.radix", 16, {"radix": 64},
             {"keys_per_tile": 128, "window": "sustained"})
    out = run(c, seconds=0.01)
    assert_exact(out)
    assert out["window"]["mode"] == "sustained"
    assert out["window"]["cut_at_quantum"] == [8]    # one megastep polled


def test_traced_run_reports_readers():
    c = tiny("tiles64.fft", 8, {"points_log2": 9})
    out = run(c, traced=True)
    assert_exact(out)
    m = out["metrics"]
    # No /device: plane on the CPU: the device-trace readers find
    # nothing and stay silent; the counter, span and compile ones report.
    assert "device_idle_share" not in m
    assert m["rounds_per_kinst"]["value"] > 0
    assert m["sim_init_ms"]["value"] > 0
    assert m["compile_s"]["value"] > 0


def test_control_is_refused():
    """The program with its miss-chain path on, in the reference's place,
    comes out not correct."""
    out = run(core.control_cell(radix8(), 8))
    assert out["correct"] is False
    assert out["compared"]["values_differing"]["value"] > 0


def _unchanged(orig):
    def megarun(params, state, trace, max_quanta):
        return state
    return megarun, lambda st: None


def _altered(orig):
    import jax

    bump = jax.jit(lambda c: c.at[0].add(1000))

    def megarun(params, state, trace, max_quanta):
        st = orig(params, state, trace, max_quanta)
        return st._replace(clock=bump(st.clock))
    return megarun, lambda st: bump(st.clock)


def _half_left_out(orig):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def revert(new, old):
        keep = jnp.arange(new.shape[0]) < new.shape[0] // 2
        return jnp.where(keep, new, old)

    def megarun(params, state, trace, max_quanta):
        st = orig(params, state, trace, max_quanta)
        return st._replace(clock=revert(st.clock, state.clock),
                           cursor=revert(st.cursor, state.cursor))
    return megarun, lambda st: (revert(st.clock, st.clock),
                                revert(st.cursor, st.cursor))


@pytest.mark.parametrize("fault", [_unchanged, _altered, _half_left_out],
                         ids=["state_unchanged", "answer_altered",
                              "half_the_tiles_left_out"])
def test_faults_under_the_timed_path_are_refused(fault, monkeypatch):
    from graphite_tpu.engine import sim as simmod
    orig_setup = core.setup_program

    def setup_then_break(*a, **k):
        out = orig_setup(*a, **k)
        broken, warm = fault(simmod.megarun)
        params, trace = out[0], out[1]
        warm(simmod.Simulator(params, trace).state)   # compiled in set-up
        monkeypatch.setattr(simmod, "megarun", broken)
        return out

    monkeypatch.setattr(core, "setup_program", setup_then_break)
    out = run(radix8())
    assert out["correct"] is False
    assert out["failed"] >= 1


def test_refuses_a_run_without_a_tpu(capsys):
    import run as entry
    assert entry.main(["--workload", "tiles64.radix", "--seed", "3",
                       "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    assert '"correct"' not in out


def test_refuses_a_program_outside_its_checkout(monkeypatch, tmp_path):
    monkeypatch.setattr(core, "ROOT", str(tmp_path))
    with pytest.raises(core.HarnessError, match="not from this checkout"):
        core.import_program()


def test_compile_clock_counts_compiles():
    import jax
    import jax.numpy as jnp
    clock = core.CompileClock()
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    finally:
        clock.close()
    assert clock.count > 0 and clock.seconds > 0


def test_result_line_is_json():
    out = run(radix8(), seconds=0.2)
    line = json.dumps(out)
    back = json.loads(line)
    assert set(back) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
