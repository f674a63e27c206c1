"""The program's spans and scopes in a trace: the scope map and the scoped
time on hand-made HLO lines and events, and on traces recorded here."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import bench
import devtrace
import progtrace

MS = 1_000_000
HOST = dict(op_lines=("tf_XLA",), device_prefix=None)
NEW = ("update_cpu_ms.lm", "update_blocked_ms.lm", "gc_ms.lm",
       "layers_ms.lm", "vocab_ms.lm", "optimizer_ms.lm", "unscoped_ms.lm")
OLD = ("compile_s", "dispatch_ms.lm", "idle_share.lm", "mfu.lm")


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py",
                             f"metric_{name}")


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/vmap(jvp(layers))/while/body/dot_general", "layers"),
    ("jit(step)/vmap(transpose(jvp(layers)))/while", "layers"),
    ("jit(stepped)/vmap(optimizer)/add", "optimizer"),
    ("jit(step)/embed/gather", "embed"),
    # the outermost scope wins
    ("jit(step)/head/optimizer/mul", "head"),
    # joined by XLA: the first that names a scope
    ("jit(step)/jvp()/add;jit(step)/vmap(jvp(head))/mul", "head"),
    ("jit(step)/vmap(jvp(embed))/x;jit(step)/layers/y", "embed"),
    ("jit(step)/vmap(jvp())/concatenate", None),
    ("jit(step)/headroom/add", None),
    ("pop_state.params['embed']", None),
])
def test_scope_of_op_names(op_name, scope):
    assert progtrace.scope_of(op_name) == scope


def test_scope_map_from_hlo_text():
    text = "\n".join([
        "ENTRY %main (p: f32[4]) -> f32[4] {",
        '  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(s)/vmap(jvp(embed))/gather" '
        'source_file="lm.py"}',
        '  %while.7 = (s32[], f32[4]) while((s32[], f32[4]) %t), '
        'condition=%c, body=%b, '
        'metadata={op_name="jit(s)/vmap(transpose(jvp(layers)))/while"}',
        "  %copy.2 = f32[4]{0} copy(f32[4]{0} %fusion.3)",
        '  ROOT %subtract_add_fusion.1 = f32[4]{0} fusion(f32[4]{0} %a), '
        'kind=kLoop, metadata={op_name="jit(s)/vmap(optimizer)/add"}',
        '  %bitcast.9 = f32[4]{0} bitcast(f32[4]{0} %x), '
        'metadata={op_name="pop_state.params[\\\'embed\\\']"}',
        "}",
    ])
    assert progtrace.scope_map(text) == {
        "fusion.3": "embed", "while.7": "layers",
        "subtract_add_fusion.1": "optimizer"}
    assert progtrace.scope_map(None) == {}


def test_scoped_time_counts_loops_shown_whole():
    scopes = {"f.1": "embed", "while.2": "layers", "f.3": "layers",
              "f.4": "optimizer"}
    dev = {"d": [(0, 1 * MS, "f.1"),            # embed 0-1
                 (2 * MS, 1 * MS, "f.3"),       # inside the loop
                 (6 * MS, 2 * MS, "f.4"),       # optimizer 6-8
                 (8 * MS, 1 * MS, "copy.5")]}   # unscoped 8-9
    # the loop 1-5 ms: the trace shows it whole, with one body op only
    flow = {"d": [(1 * MS, 4 * MS, "while.2")]}
    got = progtrace.scoped_time(dev, flow, scopes, 0, 10 * MS)
    assert got == pytest.approx({"embed": 1e-3, "layers": 4e-3,
                                 "optimizer": 2e-3, "unscoped": 1e-3})
    busy = devtrace.reduce(dev, [], 0, 10 * MS, containers=flow)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_scoped_time_clips_to_the_window_and_averages_devices():
    scopes = {"a": "head"}
    dev = {"d0": [(0, 4 * MS, "a"), (4 * MS, 2 * MS, "b")],
           "d1": [(0, 2 * MS, "a")]}
    got = progtrace.scoped_time(dev, {}, scopes, 1 * MS, 5 * MS)
    assert got == pytest.approx({"head": 2e-3, "unscoped": 0.5e-3})


def test_counter_delta():
    before = {"update": {"count": 3, "wall_s": 1.0, "cpu_s": 0.25}}
    after = {"update": {"count": 5, "wall_s": 1.5, "cpu_s": 0.5},
             "gc": {"count": 1, "wall_s": 0.01, "cpu_s": 0.01}}
    assert progtrace.counter_delta(before, after) == {
        "update": {"count": 2, "wall_s": 0.5, "cpu_s": 0.25},
        "gc": {"count": 1, "wall_s": 0.01, "cpu_s": 0.01}}


def test_new_readers_return_nothing_from_the_harness_context():
    """What the harness's traced run gives its readers today holds no
    counters and no scoped time: the new readers return None there."""
    ctx = SimpleNamespace(compile_s=1.0, spans=bench.Spans(), units=8,
                          window_s=1.0, trace={"busy_s": 0.9,
                                               "window_s": 1.0},
                          hlo_text=None, peaks={}, devices=1,
                          flops_per_unit=1.0)
    assert {n: reader(n).read(ctx) for n in NEW} == dict.fromkeys(NEW)


def test_program_spans_leave_the_readers_unchanged(tmp_path):
    """One trace recorded here, reduced once with the harness's spans and
    once with the program's spans and the runtime's events as well: the
    window, busy time and the existing readers agree; idle gaps inside the
    program's phases are named after them or the runtime's events."""
    from repro.telemetry import RunTelemetry
    tel = RunTelemetry(None)
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    spans = bench.Spans()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with spans("step_call"), tel.phase("update"):
            y = f(x)
            time.sleep(0.002)
        with spans("wait"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = devtrace.latest_xplane(tmp_path)
    dev, host, flow = devtrace.load(path, **HOST)
    program, runtime = progtrace.load_host(path, **HOST)
    assert {n for _, _, n in program} == {"pop.update"}
    assert runtime and not {n for _, _, n in runtime} & set(devtrace.SPANS)
    lo = min(s for s, _, _ in host)
    hi = max(s + d for s, d, _ in host)
    red = devtrace.reduce(dev, host, lo, hi, containers=flow)
    red_all = devtrace.reduce(dev, host + program + runtime, lo, hi,
                              containers=flow)
    for k in ("window_s", "busy_s", "device_ops", "op_time", "op_count"):
        assert red[k] == red_all[k], k

    def ctx(trace):
        return SimpleNamespace(compile_s=0.5, spans=spans, units=3,
                               window_s=(hi - lo) * 1e-9, trace=trace,
                               hlo_text=None, peaks={"bf16_flops": 1e12},
                               devices=1, flops_per_unit=1e6)
    assert {n: reader(n).read(ctx(red)) for n in OLD} == \
        {n: reader(n).read(ctx(red_all)) for n in OLD}
    named = progtrace.idle_gaps(dev, flow, host + program + runtime, lo,
                                hi)
    assert [d for _, d in named] == [d for _, d in red["idle_gaps"]]
    # the sleeps inside the update phase: named after the harness's span
    # by the harness's reduction, after the phase (or a runtime event
    # inside it) with the program's spans
    in_call = [i for i, (n, _) in enumerate(red["idle_gaps"])
               if n == "step_call"]
    assert in_call
    assert all(named[i][0] not in devtrace.SPANS for i in in_call)
    assert "pop.update" in {n for n, _ in named}


def test_a_gap_is_named_after_the_innermost_span_covering_it():
    gap = (10 * MS, 20 * MS)
    spans = [(0, 30 * MS, "step_call"), (9 * MS, 12 * MS, "pop.update"),
             (14 * MS, 4 * MS, "Wait for usage holds"),
             (10 * MS, 6 * MS, "PjRt execute")]
    assert progtrace.attribute(gap, spans) == "PjRt execute"
    # none covers half of it: the one that covers most
    assert progtrace.attribute(gap, spans[2:3]) == "Wait for usage holds"
    assert progtrace.attribute(gap, []) == "none"


@pytest.fixture
def on_the_host(monkeypatch, tmp_path_factory):
    """This host is no chip of the table: lend it the v5e's peaks and keep
    its compile cache out of the checkout."""
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(bench, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")


def test_trace_layers_reads_every_new_metric(on_the_host):
    """The LM cell at small shapes, traced here: the seven readings are
    numbers, the scoped parts add up to the busy time and the update's
    CPU and blocked time to its wall time."""
    import trace_layers
    from test_correct import SEED, SMALL
    small = {k: v for k, v in SMALL["qwen2_0_5b.pop1.seq512"].items()
             if k != "limits"}
    r = trace_layers.run("qwen2_0_5b.pop1.seq512", SEED, 0.5, time.time(),
                         require_tpu=False, overrides=small, load_kw=HOST)
    assert r["scoped_instructions"] > 0
    assert len(r["traced"]) == 2 and r["untraced"]["rate"] > 0
    for w in r["traced"]:
        m = w["metrics"]
        assert all(isinstance(m[n], float) for n in NEW + OLD), m
        parts = sum(m[n] for n in ("layers_ms.lm", "vocab_ms.lm",
                                   "optimizer_ms.lm", "unscoped_ms.lm"))
        assert parts == pytest.approx(w["busy_ms_per_unit"], rel=0.02)
        assert min(m[n] for n in ("layers_ms.lm", "vocab_ms.lm",
                                  "optimizer_ms.lm")) > 0
        update = w["counters"]["update"]
        wall_ms = 1e3 * update["wall_s"] / update["count"]
        assert m["update_cpu_ms.lm"] + m["update_blocked_ms.lm"] == \
            pytest.approx(wall_ms, rel=0.01)
        assert update["count"] == w["units"]

