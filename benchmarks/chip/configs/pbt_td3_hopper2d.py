"""PBT-TD3 on hopper2d, built as ``repro.launch.train`` builds it.

One unit of work is one fused train-evolve epoch:
``PopTrainer.run_env_loop(pbt_interval, fused=True)``, the launcher's
``--fused-epoch`` path, with the fused Adam and fused linears, the
vectorized backend, PBT and ``donate=False``.  The weights,
hyperparameters, env states and keys come from the benchmark
(``reference.pbt_td3_hopper2d.inputs``), not from the program's own init.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flops.models import td3_epoch_flops
from record import change_norms, rms_grad_norms
from reference.pbt_td3_hopper2d import inputs

E2E = "env_steps_per_s"
CONTROL = "bfloat16"


class Cell:
    def __init__(self, cfg, traffic, seed, spans, *, fault=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.spans = spans
        self.fault = fault
        tr = traffic
        self.work_per_unit = tr["population"] * tr["num_envs"] \
            * tr["collect_steps"] * tr["pbt_interval"]
        self.flops_per_unit = td3_epoch_flops(cfg, tr)
        self.trainer = None

    # ------------------------------------------------------------ build
    def build(self):
        from repro.configs.base import HyperSpace, PopulationConfig
        from repro.envs import make
        from repro.pop import PopTrainer
        from repro.rl import get_algo, make_agent

        cfg, tr = self.cfg, self.traffic
        if self.fault == "half_batch":
            self._unplant = _plant_half_batch()
        algo, env = get_algo(cfg["algo"]), make(cfg["env"])
        space = cfg["hyper_space"]
        pcfg = PopulationConfig(
            size=tr["population"], strategy=cfg["strategy"],
            backend=cfg["backend"], num_steps=tr["updates_per_iter"],
            pbt_interval=tr["pbt_interval"], donate=cfg["donate"],
            fused_adam=cfg["fused_adam"], fused_linear=cfg["fused_linear"],
            exploit_frac=cfg["pbt"]["exploit_frac"],
            perturb_prob=cfg["pbt"]["perturb_prob"],
            perturb_scale=cfg["pbt"]["perturb_scale"],
            hyper_space=HyperSpace(
                log_uniform=tuple(map(tuple, space["log_uniform"])),
                uniform=tuple(map(tuple, space["uniform"]))))
        agent = make_agent(algo.name, env.spec, hidden=tuple(cfg["hidden"]))
        inp = inputs(cfg, tr, self.seed)
        trainer = PopTrainer(agent, pcfg, key=inp["epoch_key"])
        trainer.attach_rollout(env, num_envs=tr["num_envs"],
                               collect_steps=tr["collect_steps"],
                               batch_size=cfg["batch_size"],
                               buffer_capacity=cfg["replay_capacity"],
                               eval_envs=tr["eval_envs"])
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        st = trainer.state
        trainer.state = st._replace(
            actor=inp["actor"], critic=inp["critic"],
            target_actor=inp["actor"], target_critic=inp["critic"],
            actor_opt=zeros(st.actor_opt), critic_opt=zeros(st.critic_opt),
            step=zeros(st.step), key=inp["member_keys"])
        trainer.hypers = inp["hypers"]
        r = trainer.rollout
        r.vstate = r.vstate._replace(env_state=inp["env_state"],
                                     obs=inp["obs"])
        trainer.key = inp["epoch_key"]
        self.trainer = trainer
        self._init = {"actor": inp["actor"], "critic": inp["critic"]}

    # ------------------------------------------------------------- work
    def dispatch(self, on_iter=None):
        """One epoch, dispatched; returns what to wait on."""
        t = self.trainer
        before = t.state
        with self.spans("epoch_call"):
            metrics, _ = t.run_env_loop(
                self.traffic["pbt_interval"],
                eval_every=self.traffic["eval_every"], on_iter=on_iter,
                fused=True)
        if self.fault == "unchanged":
            t.state = before
        return (t.state, t.last_fitness, metrics["critic_loss"])

    def wait(self, handle):
        with self.spans("wait"):
            jax.block_until_ready(handle)

    def probe(self, handle):
        return handle[2]

    def setup(self):
        """Build, then drive the first ``check_steps`` epochs through the
        window's own call, recording what the comparison reads."""
        self.build()
        losses, rows, lineages = [], [], []

        def on_iter(it, metrics, stats, fitness, lineage):
            rows.append(metrics["critic_loss"])
            if lineage is not None:
                lineages.append(np.asarray(lineage))

        b2 = self.cfg["adam"]["b2"]
        for step in range(self.traffic["check_steps"]):
            rows.clear()
            h = self.dispatch(on_iter)
            self.wait(h)
            did = np.asarray([float(np.max(np.abs(np.asarray(r)))) > 0
                              for r in rows])
            losses.append(np.stack([np.asarray(r) for r, d in
                                    zip(rows, did) if d]))
            if step == 0:
                st = self.trainer.state
                grad = rms_grad_norms(
                    {"actor": (st.actor_opt.nu, st.actor_opt.step),
                     "critic": (st.critic_opt.nu, st.critic_opt.step)}, b2)
        st = self.trainer.state
        self.record = {"losses": losses, "grad": grad, "lineage": lineages,
                       "change": change_norms(
                           {"actor": st.actor, "critic": st.critic},
                           self._init)}
        del self._init

    def compiled_text(self):
        """The compiled epoch program, for the kernels' shapes."""
        t, r = self.trainer, self.trainer.rollout
        tr = self.traffic
        fn = t._fused_epoch(tr["pbt_interval"], tr["eval_every"],
                            not t.strategy.null)
        return fn.lower(t.state, r.bufs, r.vstate, t.hypers,
                        t.strategy.export_state(), t.key).compile().as_text()

    def release(self):
        self.trainer = None
        if getattr(self, "_unplant", None) is not None:
            self._unplant()
            self._unplant = None

    def reference(self, dtype="float32"):
        from reference.pbt_td3_hopper2d import run
        return run(self.cfg, self.traffic, self.seed, dtype=dtype)


def _plant_half_batch():
    """Fault for the benchmark's own tests: each sampled batch keeps its
    first half twice, so every loss is the mean over half the rows.
    Returns the function that takes the fault out again."""
    from repro.rollout import engine
    orig = engine.buffer_sample

    def half(buf, key, batch_size):
        b = orig(buf, key, batch_size)
        return jax.tree.map(
            lambda x: jnp.concatenate([x[:batch_size // 2]] * 2), b)

    engine.buffer_sample = half
    return lambda: setattr(engine, "buffer_sample", orig)
