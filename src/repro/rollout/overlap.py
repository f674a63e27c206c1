"""Overlapped acting: the fused iteration split into two pipelined programs.

The serial engine (``repro.rollout.engine``) compiles collect -> insert ->
update as ONE program, so the device runs the phases strictly back-to-back
and the host blocks on the whole iteration whenever it needs a value (the
per-iteration fitness read every PBT/CEM driver performs).  This module
splits the iteration into two jitted programs —

    collect(actors, vstate, hypers, key) -> (vstate, slot, episode_stats)
    update(state, bufs, slot, hypers, key) -> (state, bufs, metrics, did)

— and software-pipelines them across iterations, exploiting JAX async
dispatch: by the time the host blocks on ``update(t)``'s results, acting
for iteration ``t+1`` is already enqueued behind it, so the device never
waits for the host and the host never waits for acting.  The ``slot`` —
one collect's worth of experience in flight between the two programs — is
double-buffered implicitly: collect writes a fresh slot while update
consumes (and with ``pcfg.donate`` donates) the previous one, so at most
two slots are ever alive.

``policy_lag`` pins the staleness semantics:

  ``lag=0`` — the parity anchor: collect(t) then update(t), sequentially,
      with the exact key-split order of the serial fused iteration
      (``kc, ks = split(key)``) — bitwise-identical results, pinned by
      ``tests/test_overlap.py`` across all four algorithms.
  ``lag=1`` — the overlapped fast path: update(t) consumes the slot
      collected at iteration t-1, i.e. the collector acts with params
      exactly ONE update behind the learner (the off-by-one property the
      tests pin).  For the off-policy kinds this is ordinary replay
      staleness; for PPO the stored per-step ``log_prob`` extras in
      ``trajectory_spec`` ARE the importance weights, so the clipped ratio
      re-weights the one-step-stale rollout exactly as designed.

The iteration-t schedule at ``lag=1`` (after a one-collect prologue) is

    1. capture ``actors(state_t)``           (host-side tree slice)
    2. dispatch update(t) on slot(t-1)       (device starts gradients)
    3. dispatch collect(t+1) with actors(state_t)
    4. return — the caller may block on update(t)'s metrics/fitness while
       collect(t+1) is still running on device

Donation: update donates (bufs, slot) but never ``state`` — the in-flight
collect still reads actor slices of the pre-update state; collect donates
``vstate``.  Staleness interactions (evolve rewrites params between
iterations; the pending slot was collected by pre-evolve actors) are the
same one-iteration staleness the knob already declares.

Not supported at ``lag=1``: ``build_epoch`` (a fused epoch is one program —
there is nothing to overlap) — use the serial engine for fused epochs.
``export_state`` drops the in-flight slot (one collect of not-yet-inserted
experience); a restore simply re-runs the prologue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data.replay_buffer import buffer_sample
from repro.rollout.engine import RolloutEngine, abstract_args, aot_mismatch
from repro.rollout.vecenv import episode_stats


class OverlapEngine(RolloutEngine):
    """RolloutEngine with the iteration split into pipelined collect/update
    programs and a ``policy_lag`` staleness knob (0 = serial parity,
    1 = overlapped)."""

    def __init__(self, agent, pcfg, env, *, policy_lag: int = 1, **kwargs):
        if policy_lag not in (0, 1):
            raise ValueError(f"policy_lag must be 0 or 1, got {policy_lag}")
        self.policy_lag = policy_lag
        super().__init__(agent, pcfg, env, **kwargs)
        donate = pcfg.donate
        self._progs = {
            "collect": jax.jit(self._build_collect(),
                               donate_argnums=(1,) if donate else ()),
            "update": jax.jit(self._build_update(),
                              donate_argnums=(1, 2) if donate else ()),
        }
        self._exec = dict(self._progs)
        self._pending = None     # (slot, stats) in flight between programs

    # ---------------------------------------------------------- programs
    def _build_collect(self):
        flat = self.kind == "replay"

        def collect(actors, vstate, hypers, key):
            vstate, slot = self.collector.collect(
                actors, vstate, key, self.collect_steps, hypers, flat=flat,
                chunk_steps=self.chunk_steps)
            return vstate, slot, episode_stats(vstate)

        return collect

    def _build_update(self):
        if self.kind != "replay":
            def update(state, bufs, slot, hypers, key):
                bufs = jax.vmap(self.exp.add)(bufs, slot)
                # batches are built with the CURRENT params' actor slices
                # exactly like the serial iteration (which computes them
                # pre-update from the same state) — GAE's value baseline
                # matches the stored `value` extras' policy via `log_prob`
                actors = self.agent.actor_params(state)
                batches = self.population_batches(bufs, actors, hypers, key)
                state, metrics = self._update_k(state, batches, hypers)
                return state, bufs, metrics, jnp.ones((), bool)

            return update

        K, n, B = self.num_steps, self.n, self.batch_size

        def update(state, bufs, slot, hypers, key):
            bufs = jax.vmap(self.exp.add)(bufs, slot)
            can = jnp.all(jax.vmap(lambda b: self.exp.ready(b, B))(bufs))

            def do_update(state):
                keys = jax.random.split(key, K * n)
                keys = keys.reshape((K, n) + keys.shape[1:])
                batches = jax.vmap(jax.vmap(
                    lambda b, kk: buffer_sample(b, kk, B)),
                    in_axes=(None, 0))(bufs, keys)          # (K, N, B, ...)
                if K == 1:
                    batches = jax.tree.map(lambda x: x[0], batches)
                return self._update_k(state, batches, hypers)

            def skip(state):
                return state, self._zero_metrics

            state, metrics = jax.lax.cond(can, do_update, skip, state)
            return state, bufs, metrics, can

        return update

    def _call(self, which, *args):
        fn = self._exec[which]
        try:
            return fn(*args)
        except (TypeError, ValueError) as e:
            if fn is self._progs[which] or not aot_mismatch(e):
                raise
            # AOT executables only accept the shapes they were lowered for
            self._exec[which] = self._progs[which]
            return self._progs[which](*args)

    # ---------------------------------------------------------- stepping
    def iterate(self, state, hypers, key):
        """One overlapped train iteration.  ``lag=0``: collect then update,
        bitwise-equal to the serial fused iteration.  ``lag=1``: update(t)
        on the pending slot is dispatched first, then collect(t+1) with the
        pre-update params — the returned ``(metrics, stats, did)`` belong
        to the consumed slot, and blocking on them does NOT wait for the
        in-flight collect."""
        if self.policy_lag == 0:
            kc, ks = jax.random.split(key)
            actors = self.agent.actor_params(state)
            self.vstate, slot, stats = self._call(
                "collect", actors, self.vstate, hypers, kc)
            state, self.bufs, metrics, did = self._call(
                "update", state, self.bufs, slot, hypers, ks)
            return state, metrics, stats, did

        if self._pending is None:
            # prologue: fill the first slot (one extra key split, once)
            key, kp = jax.random.split(key)
            actors = self.agent.actor_params(state)
            self.vstate, slot, stats = self._call(
                "collect", actors, self.vstate, hypers, kp)
            self._pending = (slot, stats)

        kc, ks = jax.random.split(key)
        actors = self.agent.actor_params(state)      # pre-update params
        slot, stats = self._pending
        new_state, self.bufs, metrics, did = self._call(
            "update", state, self.bufs, slot, hypers, ks)
        self.vstate, next_slot, next_stats = self._call(
            "collect", actors, self.vstate, hypers, kc)
        self._pending = (next_slot, next_stats)
        return new_state, metrics, stats, did

    # ------------------------------------------------------------- misc
    def build_epoch(self, **kwargs):
        if self.policy_lag == 0:
            return super().build_epoch(**kwargs)
        raise NotImplementedError(
            "fused train–evolve epochs are one jitted program — there is "
            "nothing to overlap; use the serial engine (policy_lag=None) "
            "or policy_lag=0 for fused epochs")

    def import_state(self, state):
        super().import_state(state)
        self._pending = None     # restored runs re-run the prologue

    # ------------------------------------------------- AOT warm compile
    def warm_compile_async(self, state, hypers, key):
        """AOT-compile BOTH pipelined programs on a background thread; the
        returned ``join()`` installs them (see the serial engine's
        docstring for the contract)."""
        import threading

        a_state, a_bufs, a_vstate = (abstract_args(state),
                                     abstract_args(self.bufs),
                                     abstract_args(self.vstate))
        a_h = None if hypers is None else abstract_args(hypers)
        a_key = abstract_args(key)
        box = {}

        def work():
            try:
                a_actors = jax.eval_shape(self.agent.actor_params, a_state)
                _, a_slot, _ = jax.eval_shape(
                    self._progs["collect"], a_actors, a_vstate, a_h, a_key)
                box["collect"] = self._progs["collect"].lower(
                    a_actors, a_vstate, a_h, a_key).compile()
                box["update"] = self._progs["update"].lower(
                    a_state, a_bufs, a_slot, a_h, a_key).compile()
            except Exception as e:          # pragma: no cover - defensive
                box["error"] = e

        thread = threading.Thread(target=work, daemon=True,
                                  name="repro-aot-compile")
        thread.start()

        def join():
            thread.join()
            if "update" in box:
                self._exec = {"collect": box["collect"],
                              "update": box["update"]}
            return box.get("error")

        return join
