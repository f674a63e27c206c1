"""``PopTrainer`` — the single driver for population (and single-agent)
training.

Composes an ``Agent`` adapter, an ``EvolutionStrategy`` and an
``UpdateBackend`` from one ``PopulationConfig``; population size 1 is just
``NoEvolution`` over a 1-member stack, so every consumer (the LM train CLI,
the RL examples, the benchmarks) runs the same code path.

    agent = ModuleAgent(td3, obs_dim, act_dim)
    pcfg = PopulationConfig(size=8, strategy="pbt", backend="vectorized",
                            hyper_space=space, pbt_interval=10)
    trainer = PopTrainer(agent, pcfg, seed=0)
    for it in ...:
        metrics, lineage = trainer.step(batches, fitness=returns)

Responsibilities:
  * population init (+ strategy binding, e.g. CEM's initial draw)
  * the compiled update (backend + num_steps chaining + buffer donation)
  * the fitness window, CAPPED at ``pcfg.fitness_window`` entries (the
    unbounded-list leak of the old driver is gone)
  * the evolve cadence (every ``pcfg.pbt_interval`` trainer steps; skipped
    entirely for null strategies)
  * checkpoint/resume via ``repro.checkpoint`` (state + strategy internals,
    with hypers and the attached rollout engine's buffers/env states as aux
    trees, plus size + fitness extras — everything
    ``repro.elastic.restore_elastic`` needs to resume on a different
    device count or population size)
  * device placement: ``backend="islands"`` plans (or takes ``layout=``)
    an ``repro.elastic.IslandLayout`` and places state/hypers across it.
"""
from __future__ import annotations

import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PopulationConfig
from repro.pop.backend import UpdateBackend, make_update
from repro.pop.strategy import make_strategy
from repro.telemetry import RunTelemetry


@jax.jit
def _unstack(tree):
    """Per-row slices of a stacked tree in one dispatch: one compile per
    tree structure, made in the first fused epoch, where N eager ``x[i]``
    would compile once per index — after warmup, as steady-state compiles —
    upload each index and dispatch once per leaf and row."""
    n = jax.tree.leaves(tree)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


class PopTrainer:
    def __init__(self, agent, pcfg: PopulationConfig | None = None, *,
                 seed: int = 0, key=None, strategy=None, mesh=None,
                 layout=None, checkpoint_dir=None, keep: int = 2,
                 telemetry: RunTelemetry | None = None):
        self.agent = agent
        # the telemetry object is always present (a disabled RunTelemetry
        # when none was passed), so the instrumentation below never
        # branches; all of it is host wall-clock + row dispatch — array
        # values are only ever touched on the sink's writer thread
        self.telemetry = telemetry if telemetry is not None \
            else RunTelemetry(None)
        self.pcfg = pcfg = pcfg if pcfg is not None else PopulationConfig()
        self.n = pcfg.size
        self.key = jax.random.PRNGKey(seed) if key is None else key
        self.strategy = strategy if strategy is not None else \
            make_strategy(pcfg)

        self.key, k_init, k_bind, k_hyp = jax.random.split(self.key, 4)
        self.state = agent.population_init(k_init, self.n)
        if pcfg.fused_adam and hasattr(agent, "fused_adam"):
            # opt-in kernels/pop_adam path: shared-critic agents hoist their
            # policy Adam step, module agents switch to the population-level
            # make_population_update of their rl module
            agent.fused_adam = True
        if pcfg.fused_linear and hasattr(agent, "fused_linear"):
            # opt-in kernels/pop_matmul path for the population-batched
            # linear layers inside the fused update
            agent.fused_linear = True
        self.strategy.configure_agent(agent)
        self.state = self.strategy.bind(k_bind, agent, self.state)
        self.hypers = self.strategy.init_hypers(k_hyp, self.n)

        try:
            backend = UpdateBackend(pcfg.backend)
        except ValueError:
            backend = pcfg.backend
        self.layout = None
        if backend is UpdateBackend.SHARDED:
            from repro.core.distributed import shard_population
            from repro.launch.mesh import make_host_mesh
            self.mesh = mesh if mesh is not None else make_host_mesh(model=1)
            self.state = shard_population(self.state, self.mesh)
        elif backend == "islands":
            from repro.elastic import plan_layout
            self.layout = layout if layout is not None else \
                plan_layout(len(jax.devices()), self.n)
            self.mesh = mesh if mesh is not None else self.layout.mesh
            self.state = self.layout.place(
                self.state,
                model_rules=bool(getattr(agent, "model_sharded_params",
                                         False)))
            if self.hypers is not None:
                self.hypers = self.layout.place(self.hypers)
        else:
            self.mesh = mesh
        self._update = make_update(agent, pcfg.backend,
                                   num_steps=pcfg.num_steps,
                                   donate=pcfg.donate, mesh=self.mesh)

        self._window: deque = deque(maxlen=pcfg.fitness_window)
        self.last_fitness = None  # the (N,) fitness used at the last evolve
        self.step_count = 0
        self._rollout = None
        self._mgr = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointManager
            run_meta = {"run_id": self.telemetry.run_id} \
                if self.telemetry.enabled else None
            self._mgr = CheckpointManager(checkpoint_dir, keep=keep,
                                          run_meta=run_meta)
        if self.telemetry.enabled:
            # the step-0 population-health snapshot anchors the hyper
            # trajectories tools/report.py reconstructs
            self.telemetry.record_members(0, hypers=self.hypers)

    # ------------------------------------------------------------------ run
    def step(self, batch, fitness=None):
        """One update call (``pcfg.num_steps`` chained member-steps), plus —
        on cadence — one evolve.  Returns ``(metrics, lineage)`` where
        lineage is None unless evolution ran this step.

        The ``step`` phase holds the ``update`` phase (the executable call)
        and the bookkeeping after it (fitness, the window, the evolve);
        ``step`` minus ``update`` is that bookkeeping's host time."""
        with self.telemetry.phase("step"):
            with self.telemetry.phase("update"):
                self.state, metrics = self._update(self.state, batch,
                                                   self.hypers)
            self.step_count += 1
            fit = fitness if fitness is not None \
                else self.agent.fitness_from_metrics(metrics)
            if fit is not None:
                self.report_fitness(fit)
            lineage = self._maybe_evolve()
        self.telemetry.record_iteration(self.step_count - 1, metrics=metrics)
        return metrics, lineage

    def run(self, steps: int, batch_fn, *, on_step=None):
        """Drive ``steps`` update calls.  ``batch_fn(step) -> batch``;
        ``on_step(step, metrics, lineage)`` is the logging hook.  Fitness
        comes from the agent's metrics; loops with environment-derived
        fitness call ``step(batch, fitness=...)`` (or ``report_fitness``)
        themselves."""
        metrics = None
        for step in range(self.step_count, steps):
            metrics, lineage = self.step(batch_fn(step))
            if on_step is not None:
                on_step(step, metrics, lineage)
        return metrics

    # ----------------------------------------------------------- env loop
    def attach_rollout(self, env, **engine_kwargs):
        """Attach a ``repro.rollout`` acting engine: per-member batched envs
        (``num_envs``), a population of device-resident experience buffers,
        a deterministic evaluator, and the fused train iteration — shaped
        by the agent's ``experience_kind``: collect->insert->sample->
        ``pcfg.num_steps`` chained updates for replay agents, collect->
        GAE->``epochs`` x shuffled minibatches for trajectory (ppo) agents;
        ``pcfg.backend`` picks the update implementation either way.

        ``policy_lag`` (None, 0 or 1) selects the overlapped engine
        (``repro.rollout.OverlapEngine``): 0 is the split-program parity
        anchor (bitwise-equal to the serial engine), 1 pipelines collect
        against update with one-update-stale acting params.
        ``chunk_steps`` bounds collect memory at GPU-sim env counts
        (either engine).  Returns the engine."""
        from repro.rollout.engine import RolloutEngine
        from repro.rollout.overlap import OverlapEngine
        if self._mgr is not None and self.pcfg.donate:
            raise ValueError(
                "donate=True is unsafe with a checkpoint_dir: save_async "
                "may still be serializing the population state when the "
                "next fused iteration donates (and overwrites) its buffers "
                "— build the PopulationConfig with donate=False")
        policy_lag = engine_kwargs.pop("policy_lag", None)
        self.key, k = jax.random.split(self.key)
        engine_kwargs.setdefault("mesh", self.mesh)
        engine_kwargs.setdefault("telemetry", self.telemetry)
        if policy_lag is None:
            self._rollout = RolloutEngine(self.agent, self.pcfg, env, key=k,
                                          init_state=self.state,
                                          hypers=self.hypers, **engine_kwargs)
        else:
            self._rollout = OverlapEngine(self.agent, self.pcfg, env, key=k,
                                          init_state=self.state,
                                          hypers=self.hypers,
                                          policy_lag=policy_lag,
                                          **engine_kwargs)
        if self.layout is not None:
            # the engine builds its buffers and env states on the default
            # device; spread their member axis over the islands up front
            r = self._rollout
            r.bufs, r.vstate = self.layout.place((r.bufs, r.vstate))
        return self._rollout

    @property
    def rollout(self):
        if self._rollout is None:
            raise ValueError("no acting engine: call "
                             "trainer.attach_rollout(env, ...) first")
        return self._rollout

    def env_iteration(self):
        """One fused train iteration (collect + insert + sample +
        ``num_steps`` updates), entirely on device.  Counts as one trainer
        step for the evolve cadence.  Returns ``(metrics, episode_stats,
        did_update)``; updates are skipped (did_update False) until every
        member's buffer can serve a batch."""
        r = self.rollout
        self.key, k = jax.random.split(self.key)
        with self.telemetry.phase("iterate"):
            self.state, metrics, stats, did = r.iterate(self.state,
                                                        self.hypers, k)
        self.step_count += 1
        return metrics, stats, did

    def evaluate_fitness(self):
        """Per-member fitness from deterministic evaluation episodes
        (shape (N,)); does not touch the fitness window."""
        self.key, k = jax.random.split(self.key)
        with self.telemetry.phase("eval"):
            return self.rollout.evaluator.evaluate(self.actors, k)

    def run_env_loop(self, iters: int, *, eval_every: int = 1, on_iter=None,
                     fused: bool = False, block_every: int = 0):
        """Drive ``iters`` fused iterations.  Every ``eval_every`` iterations
        the evaluator scores the population into the fitness window, and —
        exactly like ``step`` — the strategy evolves every
        ``pcfg.pbt_interval`` trainer steps (here: iterations).  CEM's
        Algorithm-1 ordering (train -> evaluate -> refit) falls out of
        ``pbt_interval=1``.  ``on_iter(it, metrics, stats, fitness,
        lineage)`` is the logging hook.  Returns the last (metrics, stats).
        (On-policy engines update from the first iteration — did_update is
        always True; replay engines warm up until buffers can sample.)

        ``fused=True`` runs the SAME loop as whole jitted train–evolve
        epochs (``RolloutEngine.build_epoch``): ``pcfg.pbt_interval``
        iterations + evaluations + the strategy's evolve execute as one
        donated device program per epoch, bit-exact against the eager path
        (``tests/test_fused_epoch.py``), with per-iteration telemetry
        reconstructed from the stacked outputs.  Alignment requirements
        (checked): ``iters`` a multiple of the epoch length, ``eval_every``
        dividing it, the per-epoch evaluation count within
        ``fitness_window``, an epoch-aligned ``step_count`` and an empty
        fitness window when evolution is active.

        ``block_every=N`` (eager loop only) blocks on the iteration's
        metrics every N iterations under ``telemetry.block``, splitting the
        telemetry into dispatch time (``phases``) vs wait time (``blocks``)
        — the instrumentation that makes the overlap win visible: a serial
        engine's block covers the whole iteration, an overlapped engine's
        only the update (acting is already enqueued behind it and is never
        waited on).  Blocking is a measurement choice, so it is off by
        default in the hot path.
        """
        if fused:
            if block_every:
                raise ValueError("block_every instruments the eager loop; "
                                 "fused epochs are one device program")
            return self._run_env_loop_fused(iters, eval_every, on_iter)
        metrics = stats = None
        for it in range(iters):
            metrics, stats, did = self.env_iteration()
            if block_every and (it + 1) % block_every == 0:
                self.telemetry.block("iterate", metrics)
            fitness = None
            if eval_every and (it + 1) % eval_every == 0:
                fitness = self.evaluate_fitness()
                self.report_fitness(fitness)
                self.telemetry.record_members(self.step_count,
                                              fitness=fitness,
                                              hypers=self.hypers)
            lineage = self._maybe_evolve()
            self.telemetry.record_iteration(
                self.step_count - 1, metrics=metrics, stats=stats,
                did_update=did)
            if on_iter is not None:
                on_iter(it, metrics, stats, fitness, lineage)
        return metrics, stats

    def _fused_epoch(self, epoch_len: int, eval_every: int, evolving: bool):
        """The compiled epoch for this shape, built once and cached (a new
        trace per distinct (epoch_len, eval_every, evolving) triple only —
        steady-state epochs re-enter the same executable)."""
        key = (epoch_len, eval_every, evolving)
        cache = getattr(self, "_epoch_cache", None)
        if cache is None:
            cache = self._epoch_cache = {}
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = self.rollout.build_epoch(
                epoch_len=epoch_len, eval_every=eval_every,
                evolve_fn=self.strategy.evolve_jit() if evolving else None,
                donate=self.pcfg.donate)
        return fn

    def _run_env_loop_fused(self, iters: int, eval_every: int, on_iter):
        r = self.rollout
        pbt = self.pcfg.pbt_interval
        evolving = bool(not self.strategy.null and pbt and iters >= pbt)
        if evolving:
            epoch_len = pbt
            if iters % epoch_len:
                raise ValueError(
                    f"fused train–evolve epochs need iters ({iters}) to be "
                    f"a multiple of pbt_interval ({epoch_len})")
            if not eval_every or epoch_len % eval_every:
                raise ValueError(
                    f"fused train–evolve epochs need eval_every "
                    f"({eval_every}) to divide pbt_interval ({epoch_len}) "
                    f"so every epoch scores the population before evolving")
            if epoch_len // eval_every > self.pcfg.fitness_window:
                raise ValueError(
                    f"{epoch_len // eval_every} evaluations per epoch "
                    f"overflow fitness_window={self.pcfg.fitness_window}: "
                    f"the eager loop would drop early rows and diverge")
            if self.step_count % epoch_len:
                raise ValueError(
                    f"step_count={self.step_count} is not epoch-aligned "
                    f"(pbt_interval={epoch_len}); the eager cadence would "
                    f"evolve mid-epoch")
            if self._window:
                raise ValueError(
                    "fitness window is non-empty at fused-epoch entry; the "
                    "eager loop would mix pre-epoch rows into the evolve "
                    "fitness")
        else:
            epoch_len = iters
            if (not self.strategy.null and pbt and eval_every
                    and (self.step_count + iters) // pbt
                    > self.step_count // pbt):
                raise ValueError(
                    f"iters={iters} from step {self.step_count} crosses an "
                    f"evolve boundary (pbt_interval={pbt}) mid-epoch; run "
                    f"a multiple of pbt_interval instead")
        n_evals = (epoch_len // eval_every) if eval_every else 0

        epoch_fn = self._fused_epoch(epoch_len, eval_every, evolving)
        metrics = stats = None
        start = self.step_count
        for _ in range(max(1, iters // epoch_len) if epoch_len else 0):
            base = self.step_count
            hypers_before = self.hypers
            with self.telemetry.phase("epoch"):
                (self.state, r.bufs, r.vstate, new_hypers, strat_state,
                 self.key, m_stack, s_stack, dids, evals, fitness,
                 lineage) = epoch_fn(self.state, r.bufs, r.vstate,
                                     self.hypers,
                                     self.strategy.export_state(), self.key)
            self.step_count += epoch_len
            metrics, stats = self._fused_epoch_bookkeeping(
                base, start, epoch_len, eval_every, n_evals, evolving,
                hypers_before, new_hypers, strat_state, m_stack, s_stack,
                dids, evals, fitness, lineage, on_iter)
        return metrics, stats

    def _fused_epoch_bookkeeping(self, base, start, epoch_len, eval_every,
                                 n_evals, evolving, hypers_before,
                                 new_hypers, strat_state, m_stack, s_stack,
                                 dids, evals, fitness, lineage, on_iter):
        """Re-emit the eager loop's per-iteration side effects (telemetry
        rows, fitness-window appends, the evolve bookkeeping, ``on_iter``)
        from one fused epoch's stacked device outputs; returns the last
        iteration's ``(metrics, stats)``.  Slicing stays on device, so the
        loop runs under ``transfer_guard("disallow")``."""
        rows = _unstack((m_stack, s_stack, dids))
        fits = _unstack(evals) if n_evals else None
        for i in range(epoch_len):
            metrics, stats, did_i = rows[i]
            fit_i = None
            if n_evals and (i + 1) % eval_every == 0:
                fit_i = fits[(i + 1) // eval_every - 1]
                if not evolving:
                    self.report_fitness(fit_i)
                self.telemetry.record_members(base + i + 1, fitness=fit_i,
                                              hypers=hypers_before)
            lin_i = None
            if evolving and i == epoch_len - 1:
                # the evolve ran on device at the end of the epoch; surface
                # it through the same telemetry rows as the eager path
                if strat_state is not None:
                    self.strategy.import_state(strat_state)
                self.hypers = new_hypers
                self.last_fitness = fitness
                self._window.clear()
                lin_i = lineage
                self.telemetry.record_evolve(
                    base + epoch_len, lineage, fitness=fitness,
                    strategy=type(self.strategy).__name__)
                if self.telemetry.enabled:
                    self.telemetry.record_members(base + epoch_len,
                                                  hypers=self.hypers)
            self.telemetry.record_iteration(base + i, metrics=metrics,
                                            stats=stats, did_update=did_i)
            if on_iter is not None:
                on_iter(base + i - start, metrics, stats, fit_i, lin_i)
        return metrics, stats

    # ---------------------------------------------------------------- evolve
    def report_fitness(self, fitness):
        """Feed externally-measured per-member fitness (episode returns)
        into the window — for loops where evaluation happens outside
        ``step`` (e.g. CEM's evaluate-after-training ordering).

        Rows stay ON DEVICE: the window only ever feeds the (jitted) evolve
        and the telemetry/checkpoint sinks, so forcing a host sync here —
        the old ``np.asarray`` — stalled every evaluation iteration for a
        value nothing on the host path reads (``tests/test_fused_epoch.py``
        pins the warm loop host-transfer-free)."""
        self._window.append(jnp.asarray(fitness))

    def fitness(self):
        """Windowed-mean per-member fitness, shape (N,) — a device value."""
        if not self._window:
            return None
        return jnp.mean(jnp.stack(list(self._window)), axis=0)

    def _maybe_evolve(self):
        """Evolve iff on cadence (every ``pcfg.pbt_interval`` trainer steps,
        non-null strategy, non-empty fitness window); the single predicate
        shared by ``step`` and ``run_env_loop``."""
        if (not self.strategy.null and self.pcfg.pbt_interval
                and self.step_count % self.pcfg.pbt_interval == 0
                and self._window):
            return self.evolve()
        return None

    def evolve(self):
        with self.telemetry.phase("evolve"), \
                self.telemetry.compile_scope("evolve"):
            # the strategy's executable (and the window mean's) compiles on
            # the FIRST evolve (after warmup flipped to "steady"); label it
            # so steady-state compile counts stay an honest recompile alarm
            self.last_fitness = self.fitness()
            self.key, k = jax.random.split(self.key)
            self.state, self.hypers, lineage = self.strategy.evolve(
                k, self.state, self.hypers, jnp.asarray(self.last_fitness))
        # pre-evolve fitness describes states that may just have been
        # replaced; start the next window fresh
        self._window.clear()
        self.telemetry.record_evolve(self.step_count, lineage,
                                     fitness=self.last_fitness,
                                     strategy=type(self.strategy).__name__)
        if self.telemetry.enabled:
            # post-evolve snapshot: the hypers the children will train with
            self.telemetry.record_members(self.step_count,
                                          hypers=self.hypers)
        return lineage

    # ------------------------------------------------------------ checkpoint
    @property
    def actors(self):
        """Stacked per-member policy params (for rollout / serving)."""
        return self.agent.actor_params(self.state)

    def save(self, extra: dict | None = None, *, blocking: bool = False):
        """Checkpoint the full elastic-resumable state: the main tree
        (population state + strategy internals), the stacked actor params
        plus hypers and the attached rollout engine's replay buffers/env
        states as aux trees, and — in the JSON extras — the population
        size and current fitness, so ``repro.elastic.restore_elastic`` can
        resize by fitness when the next run has a different device count
        or population, and ``repro.serve.ContinuousEvaluator`` can promote
        serving members from the actors aux without a trainer restore.

        Only the live fitness window is recorded: ``last_fitness``
        describes pre-evolve states that may just have been replaced
        (CEM/DvD redraw members wholesale), so right after an evolve the
        checkpoint carries no fitness and an elastic resize falls back to
        by-index selection, loudly."""
        if self._mgr is None:
            raise ValueError("PopTrainer built without checkpoint_dir")
        fit = self.fitness()
        meta = dict(extra or {}, size=self.n,
                    fitness=None if fit is None
                    else np.asarray(fit, dtype=np.float64).tolist())
        # hypers and the rollout engine state are aux trees with their own
        # templates, so a restoring trainer that lacks either (a null
        # strategy after an elastic shrink to size 1; no attached rollout)
        # can still restore the main tree; "actors" duplicates the policy
        # slice of the main tree so ``repro.serve`` can promote members
        # from a live checkpoint against an agent-derived template — no
        # optimizer/strategy/buffer restore on the serving side (the few
        # extra actor bytes are noise next to the replay buffers)
        aux = {"actors": self.actors}
        if self.hypers is not None:
            aux["hypers"] = self.hypers
        if self._rollout is not None:
            aux["rollout"] = self._rollout.export_state()
        save = self._mgr.save if blocking else self._mgr.save_async
        t0 = time.perf_counter()
        with self.telemetry.phase("ckpt"):
            save(self.step_count - 1,
                 (self.state, self.strategy.export_state()), meta, aux=aux)
        self.telemetry.record_ckpt(self.step_count - 1,
                                   time.perf_counter() - t0,
                                   blocking=blocking)

    def resume(self):
        """Restore the latest checkpoint if one exists (population state,
        hypers, strategy internals, rollout buffers/env states when an
        engine is attached, step); returns the restored step (the value
        saved by ``save``) or None.  Same-topology resume only — resuming
        onto a different population size or device count goes through
        ``repro.elastic.restore_elastic``."""
        if self._mgr is None or self._mgr.latest() is None:
            return None
        (state, strat_state), extra = self._mgr.restore(
            (self.state, self.strategy.export_state()))
        restored_n = jax.tree.leaves(self.agent.actor_params(state))[0].shape[0]
        if restored_n != self.n:
            raise ValueError(
                f"checkpoint holds a population of {restored_n} but the "
                f"config says size={self.n}; resume with the original size, "
                f"or resize explicitly via repro.elastic.restore_elastic "
                f"(launch.train: --resize auto)")
        # restored leaves are host numpy: re-establish the same placement
        # __init__ gave the fresh state (islands layout / sharded mesh)
        place = self._placement()
        self.state = place(state)
        if self.hypers is not None:
            hypers = self._mgr.restore_aux("hypers", self.hypers)
            if hypers is not None:
                self.hypers = place(hypers)
        if strat_state is not None:
            self.strategy.import_state(strat_state)
        if self._rollout is not None:
            rstate = self._mgr.restore_aux(
                "rollout", self._rollout.export_state())
            if rstate is not None:
                self._rollout.import_state(rstate)
        self.step_count = extra["step"] + 1
        return extra["step"]

    def _placement(self):
        """How this trainer places a restored host pytree: the islands
        layout, the sharded-backend mesh, or plain default-device put —
        the same choice ``__init__`` made for the fresh state (and that
        ``repro.elastic.restore_elastic`` reuses)."""
        if self.layout is not None:
            return self.layout.place
        if self.mesh is not None:
            from repro.core.distributed import shard_population
            return lambda tree: shard_population(tree, self.mesh)
        return jax.device_put

    def wait(self):
        if self._mgr is not None:
            self._mgr.wait()
