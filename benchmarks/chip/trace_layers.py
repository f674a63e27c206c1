"""Per-layer readings that the harness's traced run does not take yet, for
the LM cell: the trainer's phase counters over the traced window (host
CPU and blocked time of the ``update`` phase), Python's GC pauses, the LM
step's device time per program scope, and the idle gaps attributed over
the harness's spans, the program's spans and the runtime's host events.

    python3 benchmarks/chip/trace_layers.py --workload qwen2_0_5b.pop1.seq512 \\
        --seed <n> --seconds 10

One process, one set-up as the harness's run makes it.  Then a traced
window of ``trace_units`` units right after set-up, where the harness
traces; an untraced window of ``--seconds``; and a second traced window.
The traces are read once all three are over.
Each traced window is read by the cell's per-layer readers and by
:data:`READERS` (``metrics/<name>.py``), from a context that adds the
window's counters and the scoped device time to what the harness gives.
The last line of standard output is one JSON object; the same object goes
to ``chiprun_out/trace_layers/<workload>.<seed>.json``.  Without a TPU it
exits non-zero.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

T_START = time.time()

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402
import devtrace  # noqa: E402
import progtrace  # noqa: E402

READERS = ("update_cpu_ms.lm", "update_blocked_ms.lm", "gc_ms.lm",
           "layers_ms.lm", "vocab_ms.lm", "optimizer_ms.lm",
           "unscoped_ms.lm")
OUT_DIR = bench.ROOT / "chiprun_out" / "trace_layers"
TOP = 3


def step_text(cell):
    """Compiled HLO text of one unit: the trainer's update at the traffic's
    batch shape (a persistent-cache read of the program set-up compiled)."""
    import jax
    import jax.numpy as jnp
    t, tr = cell.trainer, cell.traffic
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["population"], tr["batch"], tr["seq_len"]), jnp.int32)}
    return t._update.lower(t.state, batch, t.hypers).compile().as_text()


def covering(gap, events, top=5):
    """The events that overlap ``gap`` most: ``[[name, overlap ms]]``."""
    out = []
    for s, d, name in events:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap > 0:
            out.append([name, overlap * 1e-6])
    return sorted(out, key=lambda x: -x[1])[:top]


def record_window(cell, spec, trace_dir):
    """One traced window of ``trace_units`` units, as the harness traces
    it, with the GC span on and the trainer's counters taken around it."""
    import jax
    spans = bench.Spans()
    cell.spans = spans
    tel = cell.trainer.telemetry
    before = tel.totals()
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    with tel.gc_span():
        units, window_s, _ = bench.measure(
            cell, spans, float("inf"), spec["traffic"]["trace_units"])
    jax.profiler.stop_trace()
    return {"trace_dir": trace_dir, "spans": spans, "units": units,
            "window_s": window_s,
            "counters": progtrace.counter_delta(before, tel.totals())}


def read_window(cell, spec, ctx_common, scopes, window, load_kw):
    """The readings of one recorded window."""
    trace_dir = window["trace_dir"]
    units, counters = window["units"], window["counters"]
    path = devtrace.latest_xplane(trace_dir)
    dev, host_spans, flow = devtrace.load(path, **load_kw)
    program, runtime = progtrace.load_host(path, **load_kw)
    shutil.rmtree(trace_dir, ignore_errors=True)
    chips = spec["workload"]["chips"]
    dev = dict(sorted(dev.items())[:chips])
    if not host_spans or not dev:
        raise bench.RunError("the trace holds no harness span or device op")
    lo = min(s for s, _, _ in host_spans)
    hi = max(s + d for s, d, _ in host_spans)
    red = devtrace.reduce(dev, host_spans, lo, hi, containers=flow)
    every_span = host_spans + program + runtime
    red["scoped_s"] = progtrace.scoped_time(dev, flow, scopes, lo, hi)
    ctx = SimpleNamespace(**ctx_common, spans=window["spans"], units=units,
                          window_s=window["window_s"], trace=red,
                          counters=counters)
    names = [m["name"] for m in spec["per_layer"]] + list(READERS)
    metrics = {}
    for name in dict.fromkeys(names):
        reader = bench.load_module(HERE / "metrics" / f"{name}.py",
                                   f"metric_{name}")
        metrics[name] = reader.read(ctx)
    gaps = []
    for d, evs in dev.items():
        gaps += devtrace.gaps(evs + flow.get(d, []), lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    in_window = [e for e in runtime if lo <= e[0] <= hi]
    runtime_ms = {}
    for _, d, name in in_window:
        runtime_ms[name] = runtime_ms.get(name, 0.0) + d * 1e-6
    return {
        "units": units, "window_s": window["window_s"],
        "rate": units * cell.work_per_unit / window["window_s"],
        "busy_ms_per_unit": 1e3 * red["busy_s"] / units,
        "metrics": metrics,
        "scoped_s": red["scoped_s"],
        "counters": counters,
        "idle_gaps": red["idle_gaps"],
        "idle_gaps_all_spans": progtrace.idle_gaps(dev, flow, every_span,
                                                   lo, hi),
        "longest_gaps": [{"ms": (e - s) * 1e-6,
                          "covered_by": covering((s, e), every_span)}
                         for s, e in gaps[:TOP]],
        "runtime_ms": dict(sorted(runtime_ms.items(),
                                  key=lambda kv: -kv[1])[:15]),
    }


def run(name, seed, seconds, t_start, *, require_tpu=True, overrides=None,
        load_kw=None):
    """Set-up, first traced window, untraced window, second traced window;
    returns the readings as a dict.  The benchmark's own tests skip the
    look for a chip, shrink the cell (``overrides``) and read a trace
    recorded on the host (``load_kw`` for ``devtrace.load``)."""
    spec = bench.cell_spec(bench.benchmark(), name)
    for key, over in (overrides or {}).items():
        spec[key] = dict(spec[key], **over)
    chips = spec["workload"]["chips"]
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise bench.RunError(f"needs {chips} TPU chip(s); JAX reports "
                             f"{len(devices)} {devices[0].platform} "
                             f"device(s)")
    bench.use_cache()
    from peaks import peaks
    compiles = bench.Compiles()
    mod = bench.load_module(spec["module"],
                            f"cfg_{spec['workload']['config']}")
    cell = mod.Cell(spec["cfg"], spec["traffic"], seed, bench.Spans())
    cell.setup()
    setup_s = time.time() - t_start
    setup_compile_s = compiles.setup_s
    trace_dir = bench.TRACE_DIR / f"{name}.layers"

    # record first and read after: the compiled text is fetched only once
    # the windows are over, so the first window follows set-up exactly as
    # the harness's traced run does
    compiles.window_open = True
    first = record_window(cell, spec, trace_dir / "first")
    spans = bench.Spans()
    cell.spans = spans
    units, window_s, _ = bench.measure(cell, spans, seconds)
    untraced = units * cell.work_per_unit / window_s
    again = record_window(cell, spec, trace_dir / "again")
    compiles.window_open = False
    compiles.close()
    if compiles.in_window:
        raise bench.RunError(f"compiled inside a window: "
                             f"{compiles.in_window}")
    hlo_text = step_text(cell)
    scopes = progtrace.scope_map(hlo_text)
    ctx_common = dict(compile_s=setup_compile_s, hlo_text=hlo_text,
                      peaks=peaks(devices[0].device_kind), devices=chips,
                      flops_per_unit=cell.flops_per_unit)
    first, again = (read_window(cell, spec, ctx_common, scopes, w,
                                load_kw or {}) for w in (first, again))
    cell.release()
    return {"workload": name, "seed": seed, "setup_s": setup_s,
            "device": bench.device_info(devices[:chips]),
            "scoped_instructions": len(scopes),
            "untraced": {"units": units, "window_s": window_s,
                         "rate": untraced},
            "traced": [first, again],
            "traced_over_untraced": [w["rate"] / untraced
                                     for w in (first, again)]}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, T_START)
    except bench.RunError as e:
        print(f"[trace_layers] {e}", file=sys.stderr, flush=True)
        return 1
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    line = json.dumps(result, allow_nan=True)
    (OUT_DIR / f"{args.workload}.{args.seed}.json").write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
