"""repro.telemetry acceptance: schema'd rows survive the JSONL round-trip,
the background writer thread is where device values become host bytes (the
main thread can stay under ``transfer_guard('disallow')`` while writing),
phase timers accumulate and clear per iteration, the compat compile
listener counts XLA compiles with honest attribution labels, and a REAL
short PBT run produces a log from which ``tools/report.py`` reconstructs
the full family tree, per-member hyper trajectories, per-phase timings and
compile counts."""
import importlib.util
import json
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HyperSpace, PopulationConfig
from repro.envs import make
from repro.pop import ModuleAgent, PopTrainer
from repro.rl import td3
from repro.telemetry import (CSVSink, ConsoleSink, JSONLSink, LatencyWindow,
                             MultiSink, NullSink, ROW_KINDS, RunTelemetry,
                             validate_row)

_spec = importlib.util.spec_from_file_location(
    "report", Path(__file__).resolve().parents[1] / "tools" / "report.py")
report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(report)


# ------------------------------------------------------------------ sinks
def test_jsonl_roundtrip_every_known_kind(tmp_path):
    """One schema-valid row of every registered kind survives the JSONL
    round-trip bit-exact (and the loader sees them in write order)."""
    samples = {
        "run": {"run_id": "r1"},
        "iter": {"step": 0, "phases": {"update": 0.5}},
        "members": {"step": 0, "fitness": [1.0, 2.0]},
        "evolve": {"step": 2, "parents": [1, 1, 0]},
        "compile": {"event": "backend_compile_duration", "secs": 0.1,
                    "label": "warmup"},
        "ckpt": {"step": 4, "secs": 0.01},
        "serve": {"count": 3, "p50_ms": 1.0, "p99_ms": 2.0},
        "promotion": {"step": 4, "members": [0, 2]},
        "engine": {"algo": "ModuleAgent"},
        "profile": {"action": "start"},
        "bench": {"bench": "actor_loop"},
    }
    assert set(samples) == set(ROW_KINDS)
    path = tmp_path / "t.jsonl"
    with JSONLSink(path, strict=True) as sink:
        for kind, body in samples.items():
            sink.write(dict(body, kind=kind, t=1.0))
    rows = report.load_rows(path)
    assert rows == [dict(b, kind=k, t=1.0) for k, b in samples.items()]
    assert report.check_rows(rows) == []


def test_sink_stamps_missing_t(tmp_path):
    with JSONLSink(tmp_path / "t.jsonl") as sink:
        sink.write({"kind": "custom"})
        sink.write({"kind": "custom"})
    t = [r["t"] for r in report.load_rows(tmp_path / "t.jsonl")]
    assert all(isinstance(x, float) for x in t) and t[0] <= t[1]


def test_close_drains_background_thread(tmp_path):
    """Everything written before close() is on disk after close() —
    the writer thread is drained, not abandoned."""
    path = tmp_path / "t.jsonl"
    sink = JSONLSink(path)
    for i in range(500):
        sink.write({"kind": "custom", "i": i})
    sink.close()
    rows = report.load_rows(path)
    assert [r["i"] for r in rows] == list(range(500))


def test_device_fetch_happens_on_worker_thread(tmp_path):
    """THE design point: the main thread writes rows carrying live jax
    arrays while holding transfer_guard('disallow'); the sink's worker
    thread (where the guard, being thread-local, does not apply) fetches
    them.  This is what lets the fused-call transfer-guard tests run with
    a live sink attached."""
    path = tmp_path / "t.jsonl"
    arr = jnp.arange(4.0) + 1.0
    jax.block_until_ready(arr)
    with JSONLSink(path, strict=True) as sink:
        with jax.transfer_guard("disallow"):
            sink.write({"kind": "iter", "step": 0,
                        "phases": {}, "metrics": {"loss": arr},
                        "scalar": arr.sum()})
            sink.flush()   # worker converted while we stayed guarded
    (row,) = report.load_rows(path)
    assert row["metrics"]["loss"] == [1.0, 2.0, 3.0, 4.0]
    assert row["scalar"] == 10.0


def test_nonfinite_floats_are_stringified(tmp_path):
    with JSONLSink(tmp_path / "t.jsonl") as sink:
        sink.write({"kind": "custom", "bad": float("nan"),
                    "worse": np.float32("inf")})
    (row,) = report.load_rows(tmp_path / "t.jsonl")   # still valid JSON
    assert row["bad"] == "nan" and row["worse"] == "inf"


def test_validate_row_and_strict_close(tmp_path):
    assert validate_row({"kind": "iter", "t": 0.0, "step": 1,
                         "phases": {}}) is None
    assert "lacks required fields" in validate_row(
        {"kind": "evolve", "t": 0.0, "step": 1})
    assert "kind" in validate_row({"t": 0.0})
    # non-strict: invalid rows are dropped, the run survives
    sink = JSONLSink(tmp_path / "drop.jsonl")
    sink.write({"kind": "evolve"})      # missing step/parents
    sink.write({"kind": "custom"})
    sink.close()
    assert len(report.load_rows(sink.path)) == 1
    # strict: close() raises, naming the offense
    strict = JSONLSink(tmp_path / "strict.jsonl", strict=True)
    strict.write({"kind": "evolve"})
    with pytest.raises(ValueError, match="evolve row lacks"):
        strict.close()


def test_csv_sink_one_file_per_kind(tmp_path):
    with CSVSink(tmp_path / "run.csv") as sink:
        sink.write({"kind": "iter", "t": 0.0, "step": 0,
                    "phases": {"u": 0.5}})
        sink.write({"kind": "iter", "t": 1.0, "step": 1,
                    "phases": {"u": 0.6}, "extra": 9})   # projected away
        sink.write({"kind": "ckpt", "t": 2.0, "step": 1, "secs": 0.1})
    it = (tmp_path / "run.iter.csv").read_text().splitlines()
    assert it[0] == "kind,t,step,phases"
    assert len(it) == 3 and it[2].startswith("iter,1.0,1,")
    assert (tmp_path / "run.ckpt.csv").exists()


def test_console_sink_throttles_and_quiets(capsys):
    with ConsoleSink(every=2) as sink:
        for step in range(4):
            sink.write({"kind": "iter", "t": 0.0, "step": step,
                        "phases": {}})
        sink.write({"kind": "evolve", "t": 0.5, "step": 4,
                    "parents": [1, 0]})
        sink.write({"kind": "compile", "t": 0.6, "event": "e", "secs": 0.1,
                    "label": "warmup"})
    out = capsys.readouterr().out
    assert "[iter 0]" in out and "[iter 2]" in out
    assert "[iter 1]" not in out and "[iter 3]" not in out
    assert "parents=[1, 0]" in out          # identities, not mean/max
    assert "compile" not in out             # QUIET kind: JSONL-only


def test_multisink_fans_out(tmp_path):
    a, b = JSONLSink(tmp_path / "a.jsonl"), JSONLSink(tmp_path / "b.jsonl")
    with MultiSink([a, b]) as sink:
        sink.write({"kind": "custom", "x": 1})
    rows_a, rows_b = report.load_rows(a.path), report.load_rows(b.path)
    strip = lambda rows: [{k: v for k, v in r.items() if k != "t"}
                          for r in rows]   # each sink stamps its own t
    assert strip(rows_a) == strip(rows_b) == [{"kind": "custom", "x": 1}]


# ----------------------------------------------------------- RunTelemetry
def test_disabled_telemetry_is_inert():
    tel = RunTelemetry(None)
    assert not tel.enabled and isinstance(tel.sink, NullSink)
    with tel.phase("update"):
        pass
    tel.record_iteration(0, metrics={"x": 1})
    tel.record_evolve(0, [0, 1])
    tel.close()   # nothing registered, nothing raised


def test_phase_timers_accumulate_and_clear(tmp_path):
    tel = RunTelemetry(JSONLSink(tmp_path / "t.jsonl", strict=True))
    for _ in range(2):                    # re-entry accumulates
        with tel.phase("update"):
            time.sleep(0.01)
    with tel.phase("evolve"):
        time.sleep(0.005)
    tel.record_iteration(0)
    tel.record_iteration(1)               # phases were cleared
    tel.close()
    rows = [r for r in report.load_rows(tmp_path / "t.jsonl")
            if r["kind"] == "iter"]
    assert rows[0]["phases"]["update"] >= 0.02
    assert rows[0]["phases"]["evolve"] >= 0.005
    assert rows[1]["phases"] == {}
    # CPU time beside wall time: a sleeping phase barely uses the CPU
    assert set(rows[0]["phases_cpu"]) == {"update", "evolve"}
    assert rows[0]["phases_cpu"]["update"] < rows[0]["phases"]["update"]
    assert rows[1]["phases_cpu"] == {}
    # row timestamps are monotone within one producer
    ts = [r["t"] for r in report.load_rows(tmp_path / "t.jsonl")]
    assert ts == sorted(ts)


def _spin(secs):
    t = time.perf_counter()
    while time.perf_counter() - t < secs:
        pass


def test_totals_split_wall_into_cpu_and_blocked():
    """``totals()`` is cumulative across ``record_iteration`` and splits a
    phase's wall time into this thread's CPU time and the rest, the time
    it waited: a sleeping phase reads CPU ~ 0 and blocked ~ the sleep, a
    spinning one CPU ~ wall."""
    tel = RunTelemetry(None)
    with tel.phase("sleep"):
        time.sleep(0.05)
    tel.record_iteration(0)
    with tel.phase("spin"):
        _spin(0.05)
    with tel.phase("sleep"):
        time.sleep(0.05)
    tel.record_iteration(1)
    tot = tel.totals()
    assert set(tot) == {"sleep", "spin"}
    sleep, spin = tot["sleep"], tot["spin"]
    assert sleep["count"] == 2 and spin["count"] == 1
    assert sleep["wall_s"] >= 0.1 and spin["wall_s"] >= 0.05
    assert sleep["cpu_s"] < 0.25 * sleep["wall_s"]
    assert sleep["wall_s"] - sleep["cpu_s"] >= 0.09
    # a loaded host may take the CPU from the spinning thread now and then
    assert spin["cpu_s"] >= 0.5 * spin["wall_s"]
    assert tel.totals() == tot   # reading does not clear


def _trace_events(trace_dir, names):
    """``{name: [(start_ns, end_ns)]}`` of the host events named in
    ``names`` in the profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_phases_are_trace_annotations_update_inside_step(tmp_path):
    """Each phase is a profiler annotation ``pop.<phase>`` on the trace's
    clock — with a disabled telemetry too, which is what a trainer built
    without one has — and ``update`` lies inside ``step``."""
    tel = RunTelemetry(None)
    f = jax.jit(lambda x: jnp.sin(x).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with tel.phase("step"):
        with tel.phase("update"):
            f(x).block_until_ready()
        _spin(0.002)
    jax.profiler.stop_trace()
    ev = _trace_events(tmp_path, {"pop.step", "pop.update", "step",
                                  "update"})
    assert set(ev) == {"pop.step", "pop.update"}
    ((s0, s1),), ((u0, u1),) = ev["pop.step"], ev["pop.update"]
    assert s0 <= u0 < u1 <= s1 and u1 - u0 < s1 - s0


def test_gc_span_records_a_forced_collection(tmp_path):
    """Inside ``gc_span`` a collection is a ``gc`` annotation in the trace
    and a count in ``totals()``; outside it nothing is hooked."""
    import gc
    tel = RunTelemetry(None)
    jax.profiler.start_trace(str(tmp_path))
    with tel.gc_span():
        gc.collect()
    jax.profiler.stop_trace()
    assert tel._on_gc not in gc.callbacks
    gc.collect()
    g = tel.totals()["gc"]
    assert g["count"] >= 1 and g["wall_s"] > 0
    assert len(_trace_events(tmp_path, {"gc"})["gc"]) == g["count"]
    # a profile traces collections until it stops
    tel.start_profile(tmp_path / "profile")
    gc.collect()
    tel.stop_profile()
    n = tel.totals()["gc"]["count"]
    assert n > g["count"]
    gc.collect()
    assert tel.totals()["gc"]["count"] == n
    assert tel._on_gc not in gc.callbacks


def test_compile_listener_counts_labels_and_unregisters(tmp_path):
    tel = RunTelemetry(JSONLSink(tmp_path / "t.jsonl", strict=True))

    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(3.0)).block_until_ready()
    assert tel.compile_count >= 1
    warm = tel.compile_count

    tel.record_iteration(0)               # warmup -> steady flip
    with tel.compile_scope("resize"):
        jax.jit(lambda x: x * 3.0 - 7.0)(jnp.arange(3.0)).block_until_ready()
    assert tel.compile_count > warm
    after_scope = tel.compile_count

    tel.close()                           # unregisters the listener
    jax.jit(lambda x: x * 5.0 + 11.0)(jnp.arange(3.0)).block_until_ready()
    assert tel.compile_count == after_scope

    labels = [r["label"] for r in report.load_rows(tmp_path / "t.jsonl")
              if r["kind"] == "compile"]
    assert set(labels) == {"warmup", "resize"}
    assert labels[:warm] == ["warmup"] * warm


def test_record_iteration_keeps_device_values_raw(tmp_path):
    """did_update may be a device scalar; record_iteration must not
    bool() it on the caller's thread (that would sync inside the guarded
    train loop)."""
    tel = RunTelemetry(JSONLSink(tmp_path / "t.jsonl", strict=True))
    flag = jnp.asarray(True)
    jax.block_until_ready(flag)
    with jax.transfer_guard("disallow"):
        tel.record_iteration(0, did_update=flag)
    tel.close()
    (row,) = [r for r in report.load_rows(tmp_path / "t.jsonl")
              if r["kind"] == "iter"]
    assert row["did_update"] is True


# --------------------------------------------------------- latency window
def test_latency_window_percentiles_and_fill():
    w = LatencyWindow()
    for ms in range(1, 101):
        w.add(ms / 1e3, fill=0.5, requests=2)
    w.observe_queue(3)
    w.observe_queue(7)
    s = w.summary()
    assert s["count"] == 100 and s["requests"] == 200
    assert s["p50_ms"] == pytest.approx(50.5, abs=1.0)
    assert s["p99_ms"] == pytest.approx(99.0, abs=1.5)
    assert s["fill"] == 0.5 and s["queue_depth_max"] == 7
    w.reset()
    assert w.count == 0 and w.summary()["p50_ms"] is None


# ------------------------------------------------- a real short PBT run
@pytest.fixture(scope="module")
def pbt_log(tmp_path_factory):
    """~6 fused iterations of TD3-PBT on pendulum with a live JSONL sink
    and checkpointing — the log every reconstruction test replays."""
    log_dir = tmp_path_factory.mktemp("pbt_log")
    env = make("pendulum")
    pcfg = PopulationConfig(
        size=4, strategy="pbt", num_steps=2, pbt_interval=2,
        hyper_space=HyperSpace(log_uniform=(("actor_lr", 3e-5, 3e-3),)),
        fitness_window=2, donate=False)
    tel = RunTelemetry(JSONLSink(log_dir / "telemetry.jsonl", strict=True),
                       meta={"algo": "td3", "env": "pendulum"})
    tr = PopTrainer(ModuleAgent(td3, env.spec.obs_dim, env.spec.act_dim),
                    pcfg, seed=0, checkpoint_dir=str(log_dir / "ckpt"),
                    telemetry=tel)
    tr.attach_rollout(env, num_envs=2, collect_steps=16, batch_size=16,
                      eval_envs=1, eval_steps=10)
    tr.run_env_loop(6, eval_every=1)
    tr.save(blocking=True)
    tel.close()
    return report.load_rows(log_dir / "telemetry.jsonl")


def test_pbt_log_is_schema_valid_and_complete(pbt_log):
    assert report.check_rows(pbt_log) == []
    kinds = {r["kind"] for r in pbt_log}
    assert {"run", "engine", "iter", "members", "evolve",
            "ckpt"} <= kinds
    (run,) = [r for r in pbt_log if r["kind"] == "run"]
    assert run["meta"]["algo"] == "td3" and run["jax"] == jax.__version__
    (eng,) = [r for r in pbt_log if r["kind"] == "engine"]
    assert eng["population"] == 4 and eng["experience"] == "replay"


def test_pbt_log_phase_timings_reconstruct(pbt_log):
    phases = report.phase_summary(pbt_log)
    # iterate every iteration; eval every iteration; evolve on cadence
    assert phases["iterate"]["iters"] == 6
    assert phases["eval"]["iters"] == 6
    assert phases["evolve"]["iters"] == 3
    assert all(d["secs"] > 0 for d in phases.values())
    # each phase's wall time splits into CPU and blocked time
    for d in phases.values():
        assert 0 <= d["cpu_secs"] <= d["secs"] + 1e-3
        assert d["cpu_secs"] + d["blocked_secs"] == \
            pytest.approx(d["secs"], abs=1e-3)
    assert report.phase_summary(
        [{"kind": "iter", "step": 0, "phases": {"u": 0.5}}]) == \
        {"u": {"secs": 0.5, "iters": 1, "ms_per_iter": 500.0,
               "cpu_secs": None, "blocked_secs": None}}
    iters = [r for r in pbt_log if r["kind"] == "iter"]
    assert [r["step"] for r in iters] == list(range(6))
    assert all(isinstance(r["metrics"]["critic_loss"], list)
               for r in iters)


def test_pbt_log_lineage_tree_reconstructs(pbt_log):
    evolves = [r for r in pbt_log if r["kind"] == "evolve"]
    assert [e["step"] for e in evolves] == [2, 4, 6]
    assert all(len(e["parents"]) == 4 and e["strategy"] == "PBT"
               for e in evolves)
    roots, children, current = report.lineage_tree(pbt_log)
    # replay the events by hand: the tree's live node per slot must match
    state = {i: (i, 0) for i in range(4)}
    for e in evolves:
        prev = dict(state)
        for i, p in enumerate(e["parents"]):
            if p != i:
                state[i] = (i, e["step"])
                assert (i, e["step"]) in children.get(prev[p], []) \
                    or p < 0
    assert current == state
    # every non-root node is some node's child, exactly once
    kids = [k for v in children.values() for k in v]
    assert len(kids) == len(set(kids))
    tree = "\n".join(report.render_tree(roots, children, current))
    for slot, node in current.items():
        assert f"m{node[0]}@{node[1]} *" in tree


def test_pbt_log_hyper_trajectories_reconstruct(pbt_log):
    traj = report.hyper_trajectories(pbt_log)
    assert set(traj) == {"actor_lr"}
    series = traj["actor_lr"]
    assert all(len(vals) == 4 for _, vals in series)
    # the @0 snapshot is the sampled prior; post-evolve snapshots exist
    assert series[0][0] == 0
    assert {s for s, _ in series} >= {0, 2, 4, 6}
    fits = report.fitness_series(pbt_log)
    assert len(fits) == 6 and all(len(v) == 4 for _, v in fits)


def test_pbt_log_compiles_and_ckpt(pbt_log):
    compiles = report.compile_summary(pbt_log)
    assert compiles.get("warmup", {}).get("count", 0) > 0
    # evolve executables are labeled, not lumped into steady-state noise
    assert compiles.get("steady", {}).get("count", 0) == 0
    ckpts = [r for r in pbt_log if r["kind"] == "ckpt"]
    assert len(ckpts) == 1 and ckpts[0]["secs"] > 0
    assert ckpts[0]["blocking"] is True


def test_report_renders_and_check_passes(pbt_log, tmp_path, capsys):
    import io
    buf = io.StringIO()
    report.report(pbt_log, out=buf)
    text = buf.getvalue()
    for section in ("phases", "compiles", "family tree", "lineage",
                    "hyper actor_lr", "checkpoints"):
        assert section in text
    iterate = next(line for line in text.splitlines()
                   if line.strip().startswith("iterate "))
    assert "cpu" in iterate and "blocked" in iterate
    # --check exit codes: 0 on the real log, 1 when a row is broken
    p = tmp_path / "log.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in pbt_log) + "\n")
    assert report.main([str(p), "--check"]) == 0
    capsys.readouterr()
    p.write_text('{"kind": "evolve", "t": 1.0}\n')
    assert report.main([str(p), "--check"]) == 1


def test_checkpoint_header_carries_run_id(pbt_log, tmp_path_factory):
    """CheckpointManager run_meta: the saved extras point back at the
    telemetry run that produced them."""
    from repro.checkpoint import CheckpointManager
    (run,) = [r for r in pbt_log if r["kind"] == "run"]
    log_root = Path(tmp_path_factory.getbasetemp())
    ckpt_dirs = list(log_root.glob("pbt_log*/ckpt"))
    assert ckpt_dirs, "fixture saved a checkpoint"
    mgr = CheckpointManager(str(ckpt_dirs[0]))
    extra = mgr.peek_extra(mgr.latest())
    assert extra["run"]["run_id"] == run["run_id"]
