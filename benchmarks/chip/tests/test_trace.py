"""The trace reduction on hand-made events and on a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

import devtrace

MS = 1_000_000


def test_busy_is_the_union_of_overlapping_events():
    evs = [(0, 4 * MS, "a"), (2 * MS, 4 * MS, "b"), (10 * MS, 1 * MS, "a")]
    assert devtrace.busy_ns(evs, 0, 20 * MS) == 7 * MS
    # clipped to the window
    assert devtrace.busy_ns(evs, 3 * MS, 10 * MS + MS // 2) == \
        3 * MS + MS // 2


def test_time_per_op_name():
    evs = [(0, 4 * MS, "a"), (2 * MS, 4 * MS, "b"), (10 * MS, 1 * MS, "a")]
    ops = devtrace.op_time(evs, 0, 20 * MS)
    assert ops == pytest.approx({"a": 5e-3, "b": 4e-3})


def test_idle_gap_goes_to_the_enclosing_span():
    dev = {"tpu0": [(0, 4 * MS, "step"), (9 * MS, 1 * MS, "step")]}
    spans = [(0, 3 * MS, "step_call"), (3 * MS, 7 * MS, "next_batch"),
             (0, 10 * MS, "wait")]
    red = devtrace.reduce(dev, spans, 0, 10 * MS)
    assert red["busy_s"] == pytest.approx(5e-3)
    assert red["window_s"] == pytest.approx(10e-3)
    # the gap 4..9 ms lies inside next_batch (and the longer wait): the
    # innermost span that covers most of it wins
    assert red["idle_gaps"] == [["next_batch", pytest.approx(5e-3)]]
    assert red["op_count"] == {"step": 2}


def test_loops_are_busy_but_not_ops():
    dev = {"d": [(2 * MS, 1 * MS, "fusion.1")]}
    flow = {"d": [(0, 10 * MS, "while.3")]}
    red = devtrace.reduce(dev, [], 0, 10 * MS, containers=flow)
    assert red["busy_s"] == pytest.approx(10e-3)
    assert red["op_time"] == {"fusion.1": pytest.approx(1e-3)}


def test_op_names_from_hlo_text():
    assert devtrace.op_name(
        "%jvp_pop_matmul_.169 = f32[20,256,256]{2,1,0} custom-call(f32[2] "
        "%a), custom_call_target=\"tpu_custom_call\"") == \
        ("jvp_pop_matmul_.169", False)
    assert devtrace.op_name(
        "%while.331 = (s32[]{:T(128)}, f32[20]{0}) while((s32[], f32[20]) "
        "%tuple.1), condition=%c, body=%b") == ("while.331", True)
    assert devtrace.op_name("%conditional.16 = (f32[2]) conditional(pred[] "
                            "%p, f32[2] %x)") == ("conditional.16", True)
    assert devtrace.op_name("%fusion.2 = f32[4] fusion(f32[4] %while.25), "
                            "calls=%fc") == ("fusion.2", False)
    assert devtrace.op_name("dot_general.1") == ("dot_general.1", False)


def test_busy_averages_over_devices():
    dev = {"d0": [(0, 10 * MS, "x")], "d1": [(0, 5 * MS, "x")]}
    red = devtrace.reduce(dev, [], 0, 10 * MS)
    assert red["busy_s"] == pytest.approx(7.5e-3)
    assert red["idle_gaps"] == [["none", pytest.approx(5e-3)]]


def test_recorded_trace(tmp_path):
    """A trace recorded on this host: the host's XLA op events stand in
    for a device plane, and the harness's spans are found by name."""
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("step_call"):
        y = f(x)
    with jax.profiler.TraceAnnotation("wait"):
        y.block_until_ready()
    jax.profiler.stop_trace()
    path = devtrace.latest_xplane(tmp_path)
    dev, spans, _ = devtrace.load(path, op_lines=("tf_XLA",),
                                  device_prefix=None)
    names = {n for _, _, n in spans}
    assert names == {"step_call", "wait"}
    events = [e for evs in dev.values() for e in evs]
    assert any(name.startswith("dot") for _, _, name in events)
    lo = min(s for s, _, _ in spans)
    hi = max(s + d for s, d, _ in spans)
    red = devtrace.reduce(dev, spans, lo, hi)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert any(n.startswith("dot") for n, _ in red["device_ops"])
