"""The fused, population-vectorized train iteration (paper §4 protocol).

PR 1 compiled the update side; this module compiles the *whole* iteration —
as ONE jitted function with buffer donation, so a training iteration never
leaves the device (no host round-trips between phases, which is where the
unfused loop loses its time; see ``benchmarks/actor_loop.py``).  What the
iteration does with experience depends on the agent's declared
``experience_kind`` (the ``repro.data.experience`` protocol), and the
engine builds the matching fused variant:

  replay (off-policy: td3 / sac / dqn / shared-critic)
      collect (scan over acting steps, vmapped over members)
        -> insert into the population of device-resident replay buffers
        -> sample num_steps batches per member
        -> num_steps chained update steps
      Updates are gated on ``buffer_can_sample`` with a ``lax.cond``: until
      every member's buffer holds ``batch_size`` transitions the iteration
      only collects (metrics come back zeroed, ``did_update`` False).

  trajectory (on-policy: ppo)
      collect (same scan, time-major, recording the policy's log_prob /
      value extras) -> store the fixed-length rollout
        -> GAE on device (per-member discount / gae_lambda hypers)
        -> epochs x shuffled minibatches, chained through the SAME update
           backend (vectorized / sequential / islands) as everything else
      There is no warm-up gate: a full rollout is always consumable, so
      ``did_update`` is always True.

Either way the update count per call is one ``num_steps``-chained (replay)
or ``epochs * minibatches``-chained (trajectory) backend call, and the
whole iteration is ONE jitted donated callable.

Consumers go through ``PopTrainer.attach_rollout(env, ...)`` /
``trainer.run_env_loop(iters)``; the engine itself owns the mutable
device-side pieces (buffers + env states) that are NOT part of the
checkpointed population state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.vectorize import chain_steps
from repro.data.experience import (compute_gae, experience_ops, traj_add,
                                   traj_reset, transition_spec)
from repro.data.replay_buffer import buffer_sample
from repro.pop.backend import make_update
from repro.rollout.collector import Collector, default_exploration
from repro.rollout.evaluator import Evaluator
from repro.rollout.vecenv import VecEnv, episode_stats, reset_stats

# what a jax ``Compiled`` raises when called with arguments whose dtypes or
# shapes, shardings or pytree differ from those it was lowered for
_AOT_MISMATCH = (
    "Argument types differ from the types for which this computation was "
    "compiled",
    "Computation was compiled for input shardings that disagree",
    "Function compiled with input pytree does not match")


def aot_mismatch(err: Exception) -> bool:
    """Whether ``err`` is an AOT executable refusing its arguments — the
    one error the engines answer by falling back to their jit path.  Any
    other error (an OOM, a runtime fault) propagates."""
    return str(err).startswith(_AOT_MISMATCH)


def abstract_args(tree):
    """``ShapeDtypeStruct`` per leaf, carrying the sharding of each leaf
    committed to devices, so an AOT lowering accepts the very arrays it was
    built from (on a multi-device mesh too); uncommitted leaves stay free
    to follow the others, as they do under jit."""
    def one(x):
        committed = isinstance(x, jax.Array) and x.committed
        return jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x),
            sharding=x.sharding if committed else None)
    return jax.tree.map(one, tree)


class RolloutEngine:
    """Owns VecEnv states + the population experience buffers + the fused
    iteration.

    ``pcfg.backend`` picks the update implementation and ``pcfg.num_steps``
    the chained update count per iteration (replay kind; the trajectory
    kind derives its count from ``epochs`` x minibatches) — the same config
    knobs that drive ``PopTrainer.step``.
    """

    policy_lag = None   # serial engine; OverlapEngine overrides

    def __init__(self, agent, pcfg, env, *, key, init_state, hypers=None,
                 num_envs: int = 8, collect_steps: int = 32,
                 batch_size: int = 128, buffer_capacity: int = 100_000,
                 epochs: int = 4, eval_envs: int = 4,
                 eval_steps: int | None = None, explore_fn=None, mesh=None,
                 telemetry=None, chunk_steps: int | None = None):
        self.agent = agent
        self.telemetry = telemetry
        self.env = env
        self.n = pcfg.size
        self.num_envs = num_envs
        self.collect_steps = collect_steps
        self.batch_size = batch_size
        if chunk_steps is not None and collect_steps % chunk_steps:
            raise ValueError(f"chunk_steps={chunk_steps} must divide "
                             f"collect_steps={collect_steps}")
        self.chunk_steps = chunk_steps
        self.kind = getattr(agent, "experience_kind", "replay")
        self.exp = experience_ops(self.kind)

        explore_fn = explore_fn or default_exploration(agent)
        self.venv = VecEnv(env, num_envs)
        self.collector = Collector(self.venv, explore_fn)
        self.evaluator = Evaluator(env, explore_fn, num_envs=eval_envs,
                                   num_steps=eval_steps)

        k_env, _ = jax.random.split(key)
        self.vstate = self.collector.init(k_env, self.n)
        extras = getattr(agent, "experience_extras", ("log_prob", "value"))
        self.bufs = jax.vmap(lambda _: self.exp.init(
            env.spec, capacity=buffer_capacity, num_steps=collect_steps,
            num_envs=num_envs, extras=extras))(jnp.arange(self.n))

        if self.kind == "trajectory":
            if agent.population_level:
                raise ValueError("trajectory experience requires per-member "
                                 "agents (population-level updates consume "
                                 "replay batches)")
            rollout = collect_steps * num_envs
            if batch_size > rollout or rollout % batch_size:
                raise ValueError(
                    f"on-policy minibatch size {batch_size} must divide the "
                    f"rollout of collect_steps*num_envs = {rollout} "
                    f"transitions per member")
            self.epochs = max(1, epochs)
            self.minibatches = rollout // batch_size
            self.num_steps = self.epochs * self.minibatches
            defaults = getattr(agent, "default_hypers", {})
            self._gae_defaults = {
                "discount": defaults.get("discount", 0.99),
                "gae_lambda": defaults.get("gae_lambda", 0.95)}
        else:
            self.num_steps = max(1, pcfg.num_steps)

        if agent.population_level:
            # population_update consumes (N, B, ...) per call; chain K calls
            upd1 = make_update(agent, pcfg.backend, num_steps=1,
                               donate=False, mesh=mesh)
            self._update_k = (chain_steps(upd1, self.num_steps)
                              if self.num_steps > 1 else upd1)
        else:
            self._update_k = make_update(agent, pcfg.backend,
                                         num_steps=self.num_steps,
                                         donate=False, mesh=mesh)

        self.donate = pcfg.donate
        if self.kind == "replay":
            # the skip branch of the can-sample gate must return metrics of
            # the same structure as a real update — resolve shapes
            # abstractly once
            spec_t = transition_spec(env.spec)
            batch_s = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (self.num_steps, self.n, batch_size) + s.shape, s.dtype),
                spec_t)
            if self.num_steps == 1:
                batch_s = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                    batch_s)
            abstract = lambda t: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), t)
            _, metrics_s = jax.eval_shape(
                self._update_k, abstract(init_state), batch_s,
                None if hypers is None else abstract(hypers))
            self._zero_metrics = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), metrics_s)
            iteration = self._build_offpolicy()
        else:
            iteration = self._build_onpolicy()

        self._iteration_fn = iteration   # un-jitted; build_epoch fuses it
        self._iteration = jax.jit(
            iteration, donate_argnums=(0, 1, 2) if pcfg.donate else ())
        # what iterate() actually calls: the jit wrapper, unless an
        # AOT-compiled executable was installed (warm_compile_async)
        self._iteration_exec = self._iteration

        if telemetry is not None and telemetry.enabled:
            # the acting-side shape of the run, once, so a log is
            # self-describing (env_steps_per_iteration contextualizes every
            # iter row's phase timings)
            telemetry.record(
                "engine", algo=type(agent).__name__, experience=self.kind,
                env=env.spec.name, population=self.n, num_envs=num_envs,
                collect_steps=collect_steps, batch_size=batch_size,
                num_steps=self.num_steps, chunk_steps=chunk_steps,
                policy_lag=self.policy_lag,
                env_steps_per_iteration=self.env_steps_per_iteration)

    # --------------------------------------------------------- collect side
    def _collect_insert(self, actors, bufs, vstate, hypers, kc):
        """Collect one iteration's experience and store it: the collect-then
        -add pair both fused iterations share.  With ``chunk_steps`` set the
        trajectory is folded into the store chunk-by-chunk
        (``Collector.collect_into``) so memory stays bounded by one chunk
        per member instead of ``collect_steps × num_envs`` transitions —
        bitwise-identical results either way.  Returns ``(bufs, vstate)``."""
        flat = self.kind == "replay"
        if self.chunk_steps is not None:
            if not flat:
                # on-policy: one rollout REPLACES the last (exp.add resets
                # then appends); chunked filling resets once, then appends
                bufs = jax.vmap(traj_reset)(bufs)
                add_fn = traj_add
            else:
                add_fn = self.exp.add
            vstate, bufs = self.collector.collect_into(
                actors, vstate, bufs, add_fn, kc, self.collect_steps,
                self.chunk_steps, hypers, flat=flat)
            return bufs, vstate
        vstate, traj = self.collector.collect(
            actors, vstate, kc, self.collect_steps, hypers, flat=flat)
        return jax.vmap(self.exp.add)(bufs, traj), vstate

    # ----------------------------------------------------- off-policy fused
    def _build_offpolicy(self):
        K, n, B = self.num_steps, self.n, self.batch_size

        def iteration(state, bufs, vstate, hypers, key):
            kc, ks = jax.random.split(key)
            actors = self.agent.actor_params(state)
            with jax.named_scope("collect"):
                bufs, vstate = self._collect_insert(actors, bufs, vstate,
                                                    hypers, kc)
            can = jnp.all(jax.vmap(
                lambda b: self.exp.ready(b, B))(bufs))

            def do_update(state):
                keys = jax.random.split(ks, K * n)
                keys = keys.reshape((K, n) + keys.shape[1:])
                batches = jax.vmap(jax.vmap(
                    lambda b, kk: buffer_sample(b, kk, B)),
                    in_axes=(None, 0))(bufs, keys)          # (K, N, B, ...)
                if K == 1:
                    batches = jax.tree.map(lambda x: x[0], batches)
                return self._update_k(state, batches, hypers)

            def skip(state):
                return state, self._zero_metrics

            with jax.named_scope("update"):
                state, metrics = jax.lax.cond(can, do_update, skip, state)
            return state, bufs, vstate, metrics, episode_stats(vstate), can

        return iteration

    # ------------------------------------------------------ on-policy fused
    def member_batches(self, mbuf, actor, mhypers, key):
        """One member's GAE + shuffled epoch/minibatch stack: the rollout
        ``(T, E, ...)`` becomes update batches ``(K, B, ...)`` with
        K = epochs * minibatches (jit-able; per-member args)."""
        d = mbuf.data
        T, E = self.collect_steps, self.num_envs
        D, B, K = T * E, self.batch_size, self.num_steps
        h = dict(self._gae_defaults)
        if mhypers:
            h = {**h, **{k: mhypers[k] for k in h if k in mhypers}}
        # V(s') is evaluated on the stored pre-reset next_obs, so a
        # truncated step still bootstraps while `done` zeroes true
        # terminals; `ep_end` cuts the lambda chain at either
        next_v = self.agent.value(actor, d["next_obs"])
        ep_end = jnp.maximum(d["done"], d["truncated"])
        adv, ret = compute_gae(d["reward"], d["value"], next_v,
                               d["done"], ep_end,
                               h["discount"], h["gae_lambda"])
        flat = {"obs": d["obs"], "action": d["action"],
                "log_prob": d["log_prob"], "value": d["value"],
                "advantage": adv, "return": ret}
        flat = jax.tree.map(lambda x: x.reshape((D,) + x.shape[2:]), flat)
        idx = jax.vmap(lambda k: jax.random.permutation(k, D))(
            jax.random.split(key, self.epochs))             # (epochs, D)
        idx = idx.reshape((K, B))
        return jax.tree.map(lambda x: x[idx], flat)         # (K, B, ...)

    def population_batches(self, bufs, actors, hypers, key):
        """The whole population's update batches in the chained layout
        ``(K, N, B, ...)`` (``(N, B, ...)`` when K == 1)."""
        keys = jax.random.split(key, self.n)
        if hypers is None:
            batches = jax.vmap(
                lambda b, a, k: self.member_batches(b, a, None, k))(
                    bufs, actors, keys)
        else:
            batches = jax.vmap(self.member_batches)(bufs, actors, hypers,
                                                    keys)
        batches = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batches)
        if self.num_steps == 1:
            batches = jax.tree.map(lambda x: x[0], batches)
        return batches

    def _build_onpolicy(self):
        def iteration(state, bufs, vstate, hypers, key):
            kc, kp = jax.random.split(key)
            actors = self.agent.actor_params(state)
            with jax.named_scope("collect"):
                bufs, vstate = self._collect_insert(actors, bufs, vstate,
                                                    hypers, kc)
            with jax.named_scope("update"):
                batches = self.population_batches(bufs, actors, hypers, kp)
                state, metrics = self._update_k(state, batches, hypers)
            return (state, bufs, vstate, metrics, episode_stats(vstate),
                    jnp.ones((), bool))

        return iteration

    # -------------------------------------------------- fused train–evolve
    def build_epoch(self, *, epoch_len: int, eval_every: int = 0,
                    evolve_fn=None, donate: bool | None = None):
        """Fuse an ENTIRE train–evolve epoch into one jitted donated call.

        ``epoch_len`` iterations run in a ``lax.scan`` over the un-jitted
        fused iteration; every ``eval_every``-th iteration additionally
        scores the population with the deterministic evaluator into an
        on-device fitness accumulator (``eval_every=0`` disables); after
        the scan, ``evolve_fn`` — a pure strategy step from
        ``EvolutionStrategy.evolve_fn()`` — exploits/explores on the
        epoch-mean fitness.  Nothing leaves the device: not the per-member
        parameters between iterations, not the fitness between evaluation
        and evolve, not the strategy's distribution state (threaded through
        as ``strat_state``).

        The key chain reproduces the unfused driver bitwise: one split per
        iteration, one extra split on evaluation iterations, one before the
        evolve — the exact sequence ``PopTrainer.env_iteration`` /
        ``evaluate_fitness`` / ``evolve`` performs eagerly.

        Returns the jitted

            epoch(state, bufs, vstate, hypers, strat_state, key) ->
                (state, bufs, vstate, hypers, strat_state, key,
                 metrics_stack, stats_stack, did_stack, evals, fitness,
                 lineage)

        where the stacks carry a leading ``(epoch_len,)`` axis, ``evals``
        is the ``(num_evals, N)`` per-evaluation fitness record, and
        ``fitness`` / ``lineage`` describe the evolve (identity lineage
        when ``evolve_fn`` is None).

        Its compiled instructions carry the ``jax.named_scope`` of their
        part in their ``op_name``: ``collect`` (acting and the store),
        ``update`` (sampling and the chained updates), ``eval`` and
        ``evolve``, so that a profiler trace can be split by them.
        """
        iteration = self._iteration_fn
        evaluator = self.evaluator
        agent = self.agent
        n = self.n
        n_evals = (epoch_len // eval_every) if eval_every else 0
        if donate is None:
            donate = self.donate

        def epoch(state, bufs, vstate, hypers, strat_state, key):
            evals0 = jnp.zeros((max(n_evals, 1), n))

            def body(carry, i):
                state, bufs, vstate, key, evals = carry
                key, k_it = jax.random.split(key)
                state, bufs, vstate, metrics, stats, did = iteration(
                    state, bufs, vstate, hypers, k_it)
                if n_evals:
                    def do_eval(args):
                        key, evals = args
                        key, k_ev = jax.random.split(key)
                        fit = evaluator.evaluate(
                            agent.actor_params(state), k_ev)
                        return key, evals.at[
                            (i + 1) // eval_every - 1].set(fit)
                    with jax.named_scope("eval"):
                        key, evals = jax.lax.cond(
                            (i + 1) % eval_every == 0, do_eval,
                            lambda args: args, (key, evals))
                return ((state, bufs, vstate, key, evals),
                        (metrics, stats, did))

            carry0 = (state, bufs, vstate, key, evals0)
            (state, bufs, vstate, key, evals), (metrics, stats, dids) = \
                jax.lax.scan(body, carry0, jnp.arange(epoch_len))

            # the same reduction the trainer's fitness window performs:
            # mean over this epoch's evaluation rows, per member
            fitness = (jnp.mean(evals, axis=0) if n_evals
                       else jnp.zeros((n,)))
            if evolve_fn is not None:
                key, k_evolve = jax.random.split(key)
                with jax.named_scope("evolve"):
                    state, hypers, lineage, strat_state = evolve_fn(
                        k_evolve, state, hypers, fitness, strat_state)
            else:
                lineage = jnp.arange(n)
            return (state, bufs, vstate, hypers, strat_state, key,
                    metrics, stats, dids, evals, fitness, lineage)

        return jax.jit(epoch, donate_argnums=(0, 1, 2) if donate else ())

    # ------------------------------------------------------------- stepping
    def iterate(self, state, hypers, key):
        """One fused train iteration; returns the new population state plus
        ``(metrics, episode_stats, did_update)``."""
        try:
            out = self._iteration_exec(state, self.bufs, self.vstate,
                                       hypers, key)
        except (TypeError, ValueError) as e:
            if self._iteration_exec is self._iteration or \
                    not aot_mismatch(e):
                raise
            # an AOT executable only accepts the exact shapes it was
            # lowered for — fall back to the jit wrapper permanently
            self._iteration_exec = self._iteration
            out = self._iteration_exec(state, self.bufs, self.vstate,
                                       hypers, key)
        state, self.bufs, self.vstate, metrics, stats, did = out
        return state, metrics, stats, did

    # ---------------------------------------------------- AOT warm compile
    def warm_compile_async(self, state, hypers, key):
        """Start compiling the fused iteration ahead-of-time on a background
        thread (``jit(...).lower().compile()``) and return a ``join()``
        callable.  ``join()`` blocks until compilation finishes, installs
        the compiled executable as this engine's iteration (the lowered
        Compiled object does NOT populate the jit dispatch cache, so it must
        be kept and called directly), and returns the compile error if any
        (None on success — errors mean the engine just stays on the lazy jit
        path).

        This is the PR 3 residual closer: ``repro.elastic.restore_elastic``
        calls this before moving checkpoint data so the post-resize
        recompile overlaps the re-layout instead of serializing after it.
        """
        import threading

        args = (abstract_args(state), abstract_args(self.bufs),
                abstract_args(self.vstate),
                None if hypers is None else abstract_args(hypers),
                abstract_args(key))
        box = {}

        def work():
            try:
                box["compiled"] = self._iteration.lower(*args).compile()
            except Exception as e:          # pragma: no cover - defensive
                box["error"] = e

        thread = threading.Thread(target=work, daemon=True,
                                  name="repro-aot-compile")
        thread.start()

        def join():
            thread.join()
            if "compiled" in box:
                self._iteration_exec = box["compiled"]
            return box.get("error")

        return join

    # -------------------------------------------------- elastic re-layout
    def export_state(self):
        """The engine's mutable device state — the population of experience
        buffers and the env states (with their episode accounting) — as one
        pytree, every leaf carrying the leading population axis, so
        ``repro.elastic`` can checkpoint it and gather it by member index
        across a resize."""
        return {"bufs": self.bufs, "vstate": self.vstate}

    def import_state(self, state):
        """Install what :meth:`export_state` produced (possibly restored
        from a checkpoint and resized to this engine's population)."""
        n = jax.tree.leaves(state["bufs"])[0].shape[0]
        if n != self.n:
            raise ValueError(f"rollout state holds {n} members but the "
                             f"engine was built for {self.n}; resize with "
                             f"repro.elastic.resize_tree first")
        self.bufs = jax.tree.map(jnp.asarray, state["bufs"])
        self.vstate = jax.tree.map(jnp.asarray, state["vstate"])

    @property
    def env_steps_per_iteration(self) -> int:
        return self.collect_steps * self.num_envs * self.n

    def reset_episode_stats(self):
        self.vstate = reset_stats(self.vstate)

    def probe_obs(self, key, size: int):
        """Recent-ish observations from member 0's experience (DvD behavior
        probes and similar diagnostics)."""
        buf0 = jax.tree.map(lambda x: x[0], self.bufs)
        if self.kind == "trajectory":
            obs = buf0.data["obs"]
            return obs.reshape((-1,) + obs.shape[2:])[:size]
        return buffer_sample(buf0, key, size)["obs"]
