"""``correct`` on small shapes on the CPU: a sound run passes, and each
fault the cells can have, planted under the timed path, fails; so does
the control (the reference in the next lower precision in the program's
place).  The look for a chip is skipped; everything else is a run.

The cells' limits (``limits/<cell>.json``) are set from readings at the
cells' own sizes on the chip.  These shapes compare the same numbers
against limits of their own, set from readings at these shapes on the CPU
(PERF.md, section 6): the CPU computes the program's float32 exactly, and
a 2-layer model is not a 24-layer one.  ``test_limits.py`` holds the
cells' own limits against the chip's readings."""
import time

import pytest

import bench

SMALL = {
    "td3.pop20.utd1": {
        "cfg": {"hidden": [32, 32], "batch_size": 16,
                "replay_capacity": 5000},
        "traffic": {"population": 4, "collect_steps": 10,
                    "updates_per_iter": 4, "pbt_interval": 4,
                    "eval_every": 4, "eval_envs": 2, "trace_units": 2},
        "limits": {"loss_first_member": 1e-3, "grad_median": 5e-4,
                   "change_median": 1e-3}},
    "qwen2_0_5b.pop1.seq512": {
        "cfg": {"hidden_size": 64, "intermediate_size": 128,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 2, "vocab_size": 512},
        "traffic": {"batch": 4, "seq_len": 32, "trace_units": 3},
        "limits": {"loss": 2e-4, "grad": 0.03, "grad_median": 5e-4,
                   "change": 0.05}},
}
SEED = 3_000_000_019
CELLS = sorted(SMALL)


@pytest.fixture(autouse=True)
def on_the_host(monkeypatch, tmp_path_factory):
    """This host is no chip of the table: lend it the v5e's peaks, keep
    its compile cache out of the checkout, and see the pending cells."""
    import peaks
    from conftest import with_pending
    committed = bench.benchmark()
    monkeypatch.setattr(bench, "benchmark", lambda: with_pending(committed))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(bench, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")


def run(cell, fault=None, trace=False):
    return bench.run(cell, SEED, 0.5, trace, time.time(), require_tpu=False,
                     fault=fault, overrides=SMALL[cell])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(cell, fault):
    r = run(cell, fault)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = run(cell, "control")
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_small_shapes_compare_the_cells_numbers(cell):
    limits = bench.cell_spec(bench.benchmark(), cell)["limits"]
    assert set(SMALL[cell]["limits"]) == set(limits)


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    import functools
    import devtrace
    # no device plane on the host: its XLA threads stand in for one
    monkeypatch.setattr(devtrace, "load", functools.partial(
        devtrace.load, op_lines=("tf_XLA",), device_prefix=None))
    r = run("qwen2_0_5b.pop1.seq512", trace=True)
    assert r["correct"]
    assert "compile_s" in r["metrics"]
    assert r["device"]["busy_s"] >= 0 and "breakdown" in r
