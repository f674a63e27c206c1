"""``RunTelemetry`` — one object that turns a training/serving run into a
structured, reconstructable record.

Owned by ``PopTrainer`` (and shared with the rollout engine, the serving
stack and the launchers); everything it records flows through one
:class:`~repro.telemetry.sink.MetricsSink`, so a run log is a single JSONL
stream ``tools/report.py`` can replay into a PBT family tree, per-member
hyper trajectories, per-phase timing and compile-event counts.

Design constraint (the one that makes this engineering, not logging glue):
**nothing here may touch array values on the caller's thread.**  Phase
timers take host wall-clock (``perf_counter``) and the calling thread's
CPU time (``thread_time``) around *dispatch*: wall minus CPU is the time
the thread waited in the runtime rather than worked.  Each phase is also a
``jax.profiler.TraceAnnotation`` named ``pop.<phase>``, so a profiler trace
shows it on the device's clock.  Rows carry jax arrays by reference and
the sink's writer thread fetches them after they have materialized.  The
fused train iteration and the ensemble serve call stay ONE jitted donated
call each — asserted by the transfer-guard tests running with a live
JSONL sink attached.

Compile tracking rides ``repro.compat.register_compile_listener`` (jax's
monitoring events): every XLA backend compile becomes a ``compile`` row
stamped with the current attribution label — ``"warmup"`` until the first
iteration completes, ``"steady"`` after, or whatever an enclosing
:meth:`compile_scope` says (``launch/train.py`` wraps elastic resume in
``compile_scope("resize")``, which is exactly the compile-dominated resize
tail PR 3/PR 5 measured).
"""
from __future__ import annotations

import gc
import os
import time
from contextlib import contextmanager

import jax

from repro import compat
from repro.telemetry.sink import MetricsSink, NullSink


def _run_id() -> str:
    return f"{int(time.time()):x}-{os.getpid():x}"


def make_telemetry(log_dir=None, *, console: bool = True,
                   console_every: int = 10, meta=None) -> "RunTelemetry":
    """The launcher/example recipe: JSONL into ``log_dir/telemetry.jsonl``
    when a log dir is given, plus the console sink (iter rows throttled to
    one in ``console_every``) — the ONE formatting path that replaced the
    per-example print zoo."""
    from repro.telemetry.sink import ConsoleSink, JSONLSink, MultiSink

    sinks = []
    if log_dir:
        from pathlib import Path
        sinks.append(JSONLSink(Path(log_dir) / "telemetry.jsonl"))
    if console:
        sinks.append(ConsoleSink(every=console_every))
    if not sinks:
        return RunTelemetry(None, meta=meta)
    sink = sinks[0] if len(sinks) == 1 else MultiSink(sinks)
    return RunTelemetry(sink, meta=meta)


class RunTelemetry:
    """Phase timers + structured rows over one sink.

    ``sink=None`` builds a disabled instance (``enabled`` False): every
    method stays callable and cheap, so instrumented code never branches
    on "is telemetry on".  ``meta`` lands in the run-header row (config,
    argv, whatever identifies the run); ``track_compiles`` registers the
    compat compile listener for this object's lifetime.
    """

    def __init__(self, sink: MetricsSink | None = None, *, meta=None,
                 run_id: str | None = None, track_compiles: bool = True):
        self.enabled = sink is not None
        self.sink = sink if sink is not None else NullSink()
        self.run_id = run_id or _run_id()
        self._t0 = time.perf_counter()
        self._phases: dict[str, float] = {}
        self._phases_cpu: dict[str, float] = {}
        # cumulative [count, wall s, cpu s] per phase (and "gc"); never
        # cleared, so a reader takes differences over its own window
        self._totals: dict[str, list] = {}
        self._gc_open = None
        self._blocks: dict[str, float] = {}
        self._compile_label = "warmup"
        self.compile_count = 0
        self.compile_secs = 0.0
        self._unregister = None
        self._profiling = False
        if self.enabled:
            self.sink.write({
                "kind": "run", "run_id": self.run_id,
                "jax": jax.__version__,
                "devices": len(jax.devices()),
                "platform": jax.devices()[0].platform,
                "meta": dict(meta or {})})
            if track_compiles:
                self._unregister = compat.register_compile_listener(
                    self._on_compile)

    # -------------------------------------------------------------- timing
    def _stamp(self) -> float:
        return round(time.perf_counter() - self._t0, 6)

    @contextmanager
    def phase(self, name: str):
        """Accumulate the enclosed block's host wall-clock and this
        thread's CPU time into ``name`` for the current iteration row and
        into :meth:`totals`, under a profiler annotation ``pop.<name>``.
        Times *dispatch*, deliberately: a fused call's device time shows up
        as whichever later phase blocks on its results (or in the profiler
        trace — this is a cheap always-on timer, not a tracer).  Wall
        minus CPU is what the thread spent waiting, e.g. in the runtime
        for buffers a running step still holds."""
        t0, c0 = time.perf_counter(), time.thread_time()
        try:
            with jax.profiler.TraceAnnotation(f"pop.{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            dc = time.thread_time() - c0
            self._phases[name] = self._phases.get(name, 0.0) + dt
            self._phases_cpu[name] = self._phases_cpu.get(name, 0.0) + dc
            self._count(name, dt, dc)

    def _count(self, name: str, wall: float, cpu: float):
        total = self._totals.setdefault(name, [0, 0.0, 0.0])
        total[0] += 1
        total[1] += wall
        total[2] += cpu

    def totals(self) -> dict:
        """``{phase: {"count", "wall_s", "cpu_s"}}`` summed since this
        object was built (``record_iteration`` does not clear them), plus
        ``"gc"`` once :meth:`gc_span` or a profile has traced collections."""
        return {k: {"count": n, "wall_s": w, "cpu_s": c}
                for k, (n, w, c) in self._totals.items()}

    def _on_gc(self, phase: str, info):
        if phase == "start":
            ann = jax.profiler.TraceAnnotation("gc")
            ann.__enter__()
            self._gc_open = (ann, time.perf_counter(), time.thread_time())
        elif self._gc_open is not None:
            ann, t0, c0 = self._gc_open
            self._gc_open = None
            ann.__exit__(None, None, None)
            self._count("gc", time.perf_counter() - t0,
                        time.thread_time() - c0)

    @contextmanager
    def gc_span(self):
        """Inside the block every Python garbage collection is a profiler
        annotation ``gc`` and counts into ``totals()["gc"]``.  Meant for
        the span of a profiler trace (``start_profile`` turns it on), so
        that it costs nothing when no trace is taken."""
        self._trace_gc(True)
        try:
            yield
        finally:
            self._trace_gc(False)

    def _trace_gc(self, on: bool):
        if on:
            self._totals.setdefault("gc", [0, 0.0, 0.0])
            gc.callbacks.append(self._on_gc)
        else:
            gc.callbacks.remove(self._on_gc)
            self._gc_open = None

    def block(self, name: str, value):
        """The other half of the dispatch/block split: wait for ``value``'s
        arrays to materialize (``jax.block_until_ready``) and accumulate
        the wait into the iteration row's ``blocks`` dict.  ``phases``
        measure what the host *spends* enqueueing work; ``blocks`` measure
        what it *waits* for — a serial engine's block covers the whole
        iteration (block ≈ wall), an overlapped engine's only the update,
        because acting for the next iteration is already enqueued behind it
        and never waited on.  Blocking is a measurement choice: call sites
        opt in (``run_env_loop(block_every=...)``, benchmark drivers), the
        hot path never blocks.  Returns ``value``."""
        t0 = time.perf_counter()
        jax.block_until_ready(value)
        dt = time.perf_counter() - t0
        self._blocks[name] = self._blocks.get(name, 0.0) + dt
        return value

    # --------------------------------------------------------------- rows
    def record(self, kind: str, **fields):
        """Emit one generic row (stamped with ``t``).  The escape hatch for
        example-specific diagnostics — same pipe, same formats."""
        self.sink.write(dict(fields, kind=kind, t=self._stamp()))

    def record_iteration(self, step: int, *, metrics=None, stats=None,
                         did_update=None, **extra):
        """Close out one train iteration: the accumulated phase timers plus
        whatever the iteration produced.  ``metrics``/``stats`` may be jax
        arrays — passed by reference, fetched on the sink thread."""
        phases = {k: round(v, 6) for k, v in self._phases.items()}
        phases_cpu = {k: round(v, 6) for k, v in self._phases_cpu.items()}
        self._phases.clear()
        self._phases_cpu.clear()
        if self._compile_label == "warmup":
            self._compile_label = "steady"
        row = {"kind": "iter", "t": self._stamp(), "step": step,
               "phases": phases, "phases_cpu": phases_cpu, **extra}
        if self._blocks:
            row["blocks"] = {k: round(v, 6)
                             for k, v in self._blocks.items()}
            self._blocks.clear()
        if metrics is not None:
            row["metrics"] = metrics
        if stats is not None:
            row["stats"] = stats
        if did_update is not None:
            # may be a device scalar: no bool() here — the sink thread
            # converts, keeping this call sync-free on the train loop
            row["did_update"] = did_update
        self.sink.write(row)

    def record_members(self, step: int, *, fitness=None, hypers=None):
        """Per-member population-health snapshot: fitness and the dynamic
        hyperparameters.  The time series of these rows IS the hyper
        trajectory ``tools/report.py`` reconstructs."""
        row = {"kind": "members", "t": self._stamp(), "step": step}
        if fitness is not None:
            row["fitness"] = fitness
        if hypers is not None:
            row["hypers"] = hypers
        self.sink.write(row)

    def record_evolve(self, step: int, parents, *, fitness=None,
                      strategy=None):
        """One lineage event: ``parents[i]`` is the member whose state
        member ``i`` now holds (-1 = drawn fresh from a distribution)."""
        row = {"kind": "evolve", "t": self._stamp(), "step": step,
               "parents": parents}
        if fitness is not None:
            row["fitness"] = fitness
        if strategy is not None:
            row["strategy"] = strategy
        self.sink.write(row)

    def record_ckpt(self, step: int, secs: float, **extra):
        self.sink.write({"kind": "ckpt", "t": self._stamp(), "step": step,
                         "secs": round(secs, 6), **extra})

    # ------------------------------------------------------------ compiles
    def _on_compile(self, event: str, secs: float):
        self.compile_count += 1
        self.compile_secs += secs
        self.sink.write({"kind": "compile", "t": self._stamp(),
                         "event": event.rsplit("/", 1)[-1],
                         "secs": round(secs, 6),
                         "label": self._compile_label,
                         "count": self.compile_count})

    @contextmanager
    def compile_scope(self, label: str):
        """Attribute compilations inside the block to ``label`` (e.g.
        ``"resize"`` around an elastic re-layout, ``"promotion"`` around a
        serving-set swap of a new ensemble size)."""
        prev, self._compile_label = self._compile_label, label
        try:
            yield
        finally:
            self._compile_label = prev

    # ------------------------------------------------------------ profiler
    def start_profile(self, trace_dir):
        """Begin a ``jax.profiler`` device trace into ``trace_dir``, with
        garbage collections traced (:meth:`gc_span`) until it stops."""
        if self._profiling:
            return
        jax.profiler.start_trace(str(trace_dir))
        self._trace_gc(True)
        self._profiling = True
        self.record("profile", action="start", dir=str(trace_dir))

    def stop_profile(self):
        if not self._profiling:
            return
        self._trace_gc(False)
        jax.profiler.stop_trace()
        self._profiling = False
        self.record("profile", action="stop")

    def tick_profile(self, it: int, trace_dir, *, start: int = 1,
                     iters: int = 3):
        """Bounded profiling window for a driver loop: start the trace at
        iteration ``start`` (default 1 — after the warmup compile, so the
        trace shows steady state) and stop it ``iters`` iterations later.
        Call once per iteration; no-op when ``trace_dir`` is falsy."""
        if not trace_dir:
            return
        if it == start:
            self.start_profile(trace_dir)
        elif it == start + iters:
            self.stop_profile()

    # ------------------------------------------------------------ lifetime
    def close(self):
        """Stop the compile listener, stop any open trace, and close the
        sink (draining the writer thread)."""
        self.stop_profile()
        if self._unregister is not None:
            self._unregister()
            self._unregister = None
        self.sink.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
