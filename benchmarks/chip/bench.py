"""The harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name the benchmark gives:

* ``configs/<config>.json``: the configuration's sizes as run;
  ``configs/<config>.py``: builds the system under test from them
  (``Cell``), and ``reference/<config>.py`` is its plain reference;
* ``traffic/<traffic>.json``: the traffic mix's parameters;
* ``limits/<cell>.json``: the limit of each number the comparison with
  the reference reads;
* ``metrics/<metric>.py``: one reader per per-layer metric
  (``read(ctx) -> float | None``).

A run builds the cell, drives its first ``check_steps`` units through the
window's own call (set-up), measures for ``--seconds`` with one unit in
flight, frees the program, runs the reference and compares.  With
``--trace 1`` it traces ``trace_units`` units instead and reports the
per-layer metrics.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class RunError(Exception):
    """A run that cannot produce a result line."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise RunError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return load_json(ROOT / "BENCHMARK.json")


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_spec(bench, name, here=HERE):
    """Everything a run of cell ``name`` reads, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = Path(here)
    return {
        "workload": w,
        "config": configs[w["config"]],
        "cfg": load_json(here.parents[1] / configs[w["config"]]["file"]),
        "traffic": load_json(here / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(here / "limits" / f"{name}.json"),
        "module": here / "configs" / f"{w['config']}.py",
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def validate(bench, here=HERE):
    """Problems with ``bench`` as data: each cell's files found by name,
    its metrics readable, names well formed."""
    here = Path(here)
    errors = []
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    errors += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    for kind in ("configs", "workloads"):
        seen = [x["name"] for x in bench[kind]]
        errors += [f"duplicate {kind} name {n!r}" for n in set(seen)
                   if seen.count(n) > 1]
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    errors += [f"duplicate metric {n!r}" for n in set(metrics)
               if metrics.count(n) > 1]
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        errors.append("no setup_s among the end-to-end metrics")
    for c in bench["configs"]:
        for path in (here.parents[1] / c["file"],
                     here / "configs" / f"{c['name']}.py",
                     here / "reference" / f"{c['name']}.py"):
            if not path.is_file():
                errors.append(f"config {c['name']}: no {path.name}")
    for m in bench["per_layer"]:
        if not (here / "metrics" / f"{m['name']}.py").is_file():
            errors.append(f"per-layer metric {m['name']}: no reader")
        if m["moves"] not in e2e:
            errors.append(f"{m['name']} moves unknown {m['moves']!r}")
    for w in bench["workloads"]:
        try:
            spec = cell_spec(bench, w["name"], here)
        except (RunError, KeyError, OSError) as e:
            errors.append(f"workload {w['name']}: {e}")
            continue
        mod = load_module(spec["module"], f"cfg_{w['config']}")
        got = {m["name"] for m in spec["end_to_end"]}
        if got != {"setup_s", mod.E2E}:
            errors.append(f"workload {w['name']}: end-to-end {sorted(got)}, "
                          f"its config measures setup_s and {mod.E2E}")
        for m in spec["per_layer"]:
            if m["moves"] not in got:
                errors.append(f"workload {w['name']}: {m['name']} moves "
                              f"{m['moves']}, which it does not report")
        if not spec["per_layer"]:
            errors.append(f"workload {w['name']}: no per-layer metric")
        for key in ("check_steps", "trace_units", "population"):
            if key not in spec["traffic"]:
                errors.append(f"traffic {w['traffic']}: no {key!r}")
    return errors


def use_cache():
    """JAX's persistent compilation cache at ``CACHE_DIR``, in the checkout,
    with eviction off: an eviction pass that meets an entry without its
    access-time file fails every later write, and the cache then never
    hits."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro import compat
    compat.setup_compilation_cache()


class Spans:
    """Host spans of the harness: a profiler annotation plus seconds and
    count per name."""

    def __init__(self):
        self.total = defaultdict(float)
        self.count = defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.total[name] += time.perf_counter() - t
        self.count[name] += 1


class Compiles:
    """Backend compilations (persistent-cache reads included) by name; any
    inside the window is an error of the run."""

    def __init__(self):
        import jax
        self.setup_s = 0.0
        self.in_window = []
        self.window_open = False

        def listener(event, duration, **kwargs):
            if not event.endswith("backend_compile_duration"):
                return
            if self.window_open:
                self.in_window.append(str(kwargs.get("fun_name", "?")))
            else:
                self.setup_s += duration

        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listener)


def measure(cell, spans, seconds, max_units=None):
    """Whole units with one in flight, until ``seconds`` have passed (or
    ``max_units`` ran).  Returns ``(units, window seconds, probes)``: a
    small output of each unit, for its finiteness check afterwards."""
    t0 = time.perf_counter()
    inflight = cell.dispatch()
    probes = [cell.probe(inflight)]
    while True:
        nxt = None
        if time.perf_counter() - t0 < seconds and \
                (max_units is None or len(probes) < max_units):
            nxt = cell.dispatch()
        cell.wait(inflight)
        if nxt is None:
            break
        probes.append(cell.probe(nxt))
        inflight = nxt
    return len(probes), time.perf_counter() - t0, probes


def device_info(devices):
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(name, seed, seconds, trace, t_start, *, require_tpu=True,
        fault=None, overrides=None):
    """One run; returns the result line as a dict.  The benchmark's own
    tests skip the look for a chip, shrink the cell (``overrides``:
    ``{"cfg": {...}, "traffic": {...}}``) and plant a ``fault``;
    ``fault="control"`` puts the control (the reference in the next lower
    precision) in the program's place for the comparison."""
    spec = cell_spec(benchmark(), name)
    for key, over in (overrides or {}).items():
        spec[key] = dict(spec[key], **over)
    chips = spec["workload"]["chips"]
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise RunError(f"needs {chips} TPU chip(s); JAX reports "
                       f"{len(devices)} {devices[0].platform} device(s)")
    use_cache()
    from peaks import peaks
    pk = peaks(devices[0].device_kind)

    compiles = Compiles()
    spans = Spans()
    mod = load_module(spec["module"], f"cfg_{spec['workload']['config']}")
    cell = mod.Cell(spec["cfg"], spec["traffic"], seed, spans, fault=fault)
    cell.setup()
    setup_s = time.time() - t_start
    setup_compile_s = compiles.setup_s
    hlo_text = cell.compiled_text() if trace else None

    window_spans = Spans()
    cell.spans = window_spans
    compiles.window_open = True
    trace_dir = TRACE_DIR / name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        units, window_s, probes = measure(
            cell, window_spans, seconds, spec["traffic"]["trace_units"])
        jax.profiler.stop_trace()
    else:
        units, window_s, probes = measure(cell, window_spans, seconds)
    compiles.window_open = False
    if compiles.in_window:
        raise RunError(f"compiled inside the window: {compiles.in_window}")
    failed = sum(not bool(np.all(np.isfinite(np.asarray(p))))
                 for p in probes)
    device = device_info(devices[:chips])

    metrics = {}
    breakdown = None
    if trace:
        import devtrace as tr
        dev_events, host_spans, flow = tr.load(tr.latest_xplane(trace_dir))
        dev_events = {k: v for k, v in sorted(dev_events.items())[:chips]}
        window = [(s, s + d) for s, d, n in host_spans]
        if not window or not dev_events:
            raise RunError("the trace holds no harness span or device op")
        red = tr.reduce(dev_events, host_spans,
                        min(s for s, _ in window), max(e for _, e in window),
                        containers=flow)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [[n, s] for n, s in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
        # what the per-layer readers read
        ctx = SimpleNamespace(
            compile_s=setup_compile_s, spans=window_spans, units=units,
            window_s=window_s, trace=red, hlo_text=hlo_text, peaks=pk,
            devices=chips, flops_per_unit=cell.flops_per_unit)
        for m in spec["per_layer"]:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        rate = units * cell.work_per_unit / window_s
        for m in spec["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else \
                rate if m["name"] == mod.E2E else None
            if value is None:
                raise RunError(f"no value for end-to-end metric {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    prog_record = cell.record
    cell.release()
    if fault == "control":
        prog_record = cell.reference(dtype=mod.CONTROL)
    compared = check(cell, prog_record, spec["limits"])
    compiles.close()
    correct = failed == 0 and all(v["value"] <= v["limit"]
                                  for v in compared.values())
    result = {"correct": correct, "attempted": units, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def check(cell, prog_record, limits):
    """Every number compared with its limit: ``{name: {value, limit}}``."""
    from record import compare
    t = time.perf_counter()
    ref = cell.reference()
    print(f"reference took {time.perf_counter() - t:.1f} s", file=sys.stderr)
    numbers = compare(prog_record, ref)
    for k, (value, detail) in numbers.items():
        print(f"reading {k} {value!r} ({detail})", file=sys.stderr)
    return {k: {"value": numbers[k][0], "limit": limits[k]}
            for k in limits}


def main(argv=None, t_start=None):
    import argparse
    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start)
    except RunError as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 1
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=True), flush=True)
    return 0
