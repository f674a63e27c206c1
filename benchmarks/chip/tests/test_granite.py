"""The ``granite_4_0_h_micro.pop1.seq4096`` cell on the CPU: it validates,
its FLOPs match a hand count, its three per-layer readers give the known
answer on a hand-made HLO text and trace, and at small shapes a sound run
is ``correct`` while the control and the planted faults are not.

The small shapes compare the cell's numbers against limits of their own,
set from readings at these shapes on the CPU (five seeds each of the
program, the control and the two faults): the program read at most 4.5e-4
by ``grad_median``, the control at least 1.1e-3; by ``loss``, ``grad``
and ``change`` the program (6.2e-6, 3.4e-3, 2.0e-3) and the control
overlap at this size, so those limits sit between the program and the
faults, which read at least 8.5e-4, 0.49 and 0.088."""
import time
from types import SimpleNamespace

import pytest

import bench
from flops.hybrid import (hybrid_active_params, hybrid_flops_per_token,
                          ssd_flops_per_token)

CELL = "granite_4_0_h_micro.pop1.seq4096"
READERS = ("mamba_ms.ssm", "ssd_ms.ssm", "attn_ms.ssm")
SMALL = {
    "cfg": {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 2, "vocab_size": 512,
            "layer_types": ["mamba", "attention", "mamba"],
            "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
            "mamba_chunk_size": 8},
    "traffic": {"batch": 2, "seq_len": 32, "trace_units": 3},
    "limits": {"loss": 2e-4, "grad": 0.05, "grad_median": 8e-4,
               "change": 0.02}}
SEED = 3_000_000_019


def test_the_cell_validates_and_reads_its_files():
    b = bench.benchmark()
    assert bench.validate(b) == []
    spec = bench.cell_spec(b, CELL)
    assert spec["cfg"]["num_hidden_layers"] == 10
    assert spec["traffic"]["seq_len"] == 4096
    assert {m["name"] for m in spec["per_layer"]} >= set(READERS)
    assert [m["name"] for m in spec["end_to_end"]] == ["tokens_per_s",
                                                      "setup_s"]


# two layers, mamba then attention: d 4, MLP 8, 2 query heads of 2 and
# one KV head; Mamba-2 with expansion 2 (inner 8), 2 heads of 4, state 3,
# one group, 4 taps, chunks of 5; vocabulary 10
TINY = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 2,
        "layer_types": ["mamba", "attention", "mamba"],
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "mamba_expand": 2, "mamba_n_heads": 2, "mamba_d_head": 4,
        "mamba_d_state": 3, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_chunk_size": 5, "vocab_size": 10}


def test_hybrid_params_by_hand():
    # mamba: in_proj 4 x (8 + 8 + 3 + 3 + 2) = 96, conv (4 + 1) x 14 = 70,
    # A_log, dt_bias, D 3 x 2, gated norm 8, out_proj 8 x 4 = 32
    mamba = 96 + 70 + 6 + 8 + 32
    attention = 4 * 4 + 2 * 4 * 2 + 4 * 4      # q, k and v, o
    mlp = 3 * 4 * 8 + 2 * 4                    # gate, up, down; two norms
    assert hybrid_active_params(TINY) == mamba + attention + 2 * mlp \
        + 10 * 4 + 4


def test_hybrid_flops_per_token_by_hand():
    # SSD a token: C B^T 2 * 5 * 3, per head x 2 P-wide terms 2 * 5 * 4
    # and the state in and out 4 * 3 * 4
    ssd = 2 * 5 * 3 + 2 * (2 * 5 * 4 + 4 * 3 * 4)
    assert ssd_flops_per_token(TINY) == ssd
    scores = 4 * 7 * 2 * 2                     # S 7, 2 heads of 2
    assert hybrid_flops_per_token(TINY, 7) == \
        6 * hybrid_active_params(TINY) + 3 * (scores + ssd)


def _ctx(units=2):
    text = "\n".join([
        "ENTRY %main (p: f32[4]) -> f32[4] {",
        '  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(u)/vmap(jvp(layers))/while/body/'
        'checkpoint/mamba/dot_general"}',
        '  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(u)/vmap(transpose(jvp(layers)))/while/'
        'body/mamba/ssd/while/body/mul"}',
        '  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(u)/vmap(jvp(layers))/while/body/'
        'attention/dot_general"}',
        '  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(u)/vmap(jvp(layers))/while/body/mlp"}',
        '  ROOT %add.5 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), '
        'metadata={op_name="jit(u)/vmap(optimizer)/add"}',
        "}"])
    trace = {"op_time": {"fusion.1": 0.010, "fusion.2": 0.004,
                         "fusion.3": 0.002, "fusion.4": 0.020,
                         "add.5": 0.006}}
    return SimpleNamespace(hlo_text=text, trace=trace, units=units)


def _read(name, ctx):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py",
                             f"metric_{name}").read(ctx)


def test_readers_on_a_made_up_trace():
    ctx = _ctx()
    got = {n: _read(n, ctx) for n in READERS}
    # per unit of 2: mamba 10 + 4 ms (the scan inside it), ssd 4, attn 2
    assert got == pytest.approx({"mamba_ms.ssm": 7.0, "ssd_ms.ssm": 2.0,
                                 "attn_ms.ssm": 1.0})


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_their_scopes(name):
    ctx = _ctx().__dict__
    assert _read(name, SimpleNamespace(**dict(ctx, hlo_text=None))) is None
    assert _read(name, SimpleNamespace(**dict(ctx, trace=None))) is None
    text = "\n".join(line for line in ctx["hlo_text"].split("\n")
                     if "mamba" not in line and "attention" not in line)
    assert _read(name, SimpleNamespace(**dict(ctx, hlo_text=text))) is None


@pytest.fixture
def on_the_host(monkeypatch, tmp_path_factory):
    """This host is no chip of the table: lend it the v5e's peaks and keep
    its compile cache out of the checkout."""
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(bench, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")


def run(fault=None, trace=False):
    return bench.run(CELL, SEED, 0.5, trace, time.time(), require_tpu=False,
                     fault=fault, overrides=SMALL)


def test_sound_run_is_correct(on_the_host):
    r = run()
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["compared"]) == set(SMALL["limits"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control"])
def test_fault_is_not_correct(on_the_host, fault):
    r = run(fault)
    assert not r["correct"], r["compared"]


def test_small_shapes_compare_the_cells_numbers():
    limits = bench.cell_spec(bench.benchmark(), CELL)["limits"]
    assert set(SMALL["limits"]) == set(limits)


def test_traced_run_reports_the_scopes(on_the_host, monkeypatch):
    import functools
    import devtrace
    # no device plane on the host: its XLA threads stand in for one
    monkeypatch.setattr(devtrace, "load", functools.partial(
        devtrace.load, op_lines=("tf_XLA",), device_prefix=None))
    r = run(trace=True)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"compile_s", "mfu.lm", "idle_share.lm", *READERS} <= set(m)
    assert 0 < m["ssd_ms.ssm"] <= m["mamba_ms.ssm"]
    assert m["attn_ms.ssm"] > 0
