"""End-to-end training driver.

Two workloads behind one CLI and ONE ``PopTrainer`` code path:

  * ``--arch <id>``   — LM population training on the synthetic token
                        pipeline (the paper's §5.3-style study);
  * ``--algo <name>`` — RL population training on a pure-JAX env via the
                        fused ``repro.rollout`` iteration.  Algorithm
                        selection is the ``repro.rl.ALGOS`` *registry*
                        (td3 | sac | dqn | ppo — off- and on-policy through
                        the same experience-pipeline contract), so unknown
                        names are rejected with the valid set and adding an
                        algorithm never touches this file.

Production features exercised here (scaled down to whatever devices exist):
  * config-driven arch selection (--arch) + population size (--population)
  * the unified ``repro.pop`` API: ONE ``PopTrainer`` code path for every
    population size — size 1 is the degenerate (NoEvolution) case, so there
    is no single-agent/population branching anywhere in this file
  * the paper's protocol: one jit'd vmapped train step updates every member,
    per-member learning-rate scale as a dynamic hyperparameter
  * pluggable evolution (--strategy pbt|cem|none) and update backend
    (--backend vectorized|sequential|sharded|islands) as one-line config
    changes; islands plans an ``repro.elastic.IslandLayout`` over
    ``--devices`` accelerators (default: all of them)
  * on-device PBT exploit/explore every --pbt-interval steps (fitness =
    -loss window mean, window capped at the config's fitness_window)
  * checkpoint/restart: atomic async checkpoints every --ckpt-every steps,
    ``--resume auto`` restarts from the latest one (fault tolerance)
  * elastic restart: ``--resize auto`` accepts a checkpoint whose
    population differs from ``--population`` — the worst members are
    dropped (or PBT clones refill) via ``repro.elastic.restore_elastic``,
    so losing accelerators between runs never strands a checkpoint
  * synthetic sharded token pipeline with restart-stable streams
  * persistent XLA compilation cache (``repro.compat.setup_compilation_cache``:
    ``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``, shared with
    ``launch/serve.py``) so restarts don't pay cold compiles.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs import TrainConfig, get_config
from repro.configs.base import HyperSpace, PopulationConfig
from repro.data import host_batches
from repro.pop import LMAgent, PopTrainer
from repro.telemetry import make_telemetry


def _telemetry(args, **meta):
    """One telemetry object per run: console sink always (the single
    formatting path), JSONL into ``--log-dir`` when given (what
    ``tools/report.py`` replays), compile tracking on."""
    return make_telemetry(args.log_dir, meta=dict(
        meta, seed=args.seed, population=args.population,
        strategy=args.strategy, backend=args.backend))


def _run_rl(args):
    """RL branch: registry-selected algorithm on a pure-JAX env, trained
    through ``PopTrainer.attach_rollout`` / ``run_env_loop`` (the fused
    iteration — off-policy or on-policy per the agent's experience kind)."""
    from repro.envs import make
    from repro.rl import get_algo, make_agent

    algo = get_algo(args.algo)   # ValueError lists the registry on typos
    env = make(args.env)
    agent = make_agent(args.algo, env.spec)
    n = args.population
    print(f"[train] algo={algo.name} env={args.env} pop={n} "
          f"strategy={args.strategy} backend={args.backend} "
          f"experience={algo.experience_kind}")

    pcfg = PopulationConfig(
        size=n, strategy=args.strategy, backend=args.backend,
        num_steps=args.updates_per_iter, pbt_interval=args.pbt_interval,
        hyper_space=algo.hyper_space, donate=False,  # async ckpts read state
        fused_adam=args.fused_adam or args.fused_linear,
        fused_linear=args.fused_linear)
    layout = None
    if args.backend == "islands":
        from repro.elastic import plan_layout
        layout = plan_layout(args.devices or len(jax.devices()), n)
        print(f"[train] {layout}")
    telemetry = _telemetry(args, workload="rl", algo=algo.name, env=args.env)
    trainer = PopTrainer(agent, pcfg, seed=args.seed, layout=layout,
                         checkpoint_dir=args.ckpt_dir, telemetry=telemetry)
    trainer.attach_rollout(env, num_envs=args.num_envs,
                           collect_steps=args.collect_steps,
                           batch_size=args.batch, epochs=args.epochs,
                           policy_lag=args.policy_lag,
                           chunk_steps=args.chunk_steps)
    if args.resume == "auto":
        meta = trainer._mgr.peek_extra()   # strict: size/fitness guaranteed
        if (args.resize == "auto" and meta is not None
                and meta["size"] != n):
            from repro.elastic import restore_elastic
            with telemetry.compile_scope("resize"):
                resumed, lineage = restore_elastic(trainer)
            print(f"[train] elastic resume from step {resumed}: population "
                  f"{meta['size']} -> {n}, lineage={np.asarray(lineage)}")
        elif trainer.resume() is not None:
            print(f"[train] resumed at trainer step {trainer.step_count}")

    t0 = time.time()
    best = {"fitness": float("-inf")}

    def on_iter(it, metrics, stats, fitness, lineage):
        telemetry.tick_profile(it, args.profile, iters=args.profile_iters)
        if fitness is not None:
            best["fitness"] = max(best["fitness"],
                                  float(np.max(np.asarray(fitness))))
        if args.ckpt_every and ((it + 1) % args.ckpt_every == 0
                                or it == args.steps - 1):
            trainer.save()

    trainer.run_env_loop(args.steps, eval_every=args.eval_every,
                         on_iter=on_iter, fused=args.fused_epoch)
    trainer.wait()
    telemetry.record("run_end", best_fitness=best["fitness"],
                     compiles=telemetry.compile_count,
                     compile_secs=round(telemetry.compile_secs, 3))
    telemetry.close()
    print(f"[train] done in {time.time() - t0:.1f}s, "
          f"best fitness {best['fitness']:+.2f}")
    return best["fitness"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM config id (LM workload; exclusive with --algo)")
    ap.add_argument("--algo", default=None,
                    help="RL algorithm from the repro.rl.ALGOS registry "
                    "(td3|sac|dqn|ppo; exclusive with --arch)")
    ap.add_argument("--env", default="pendulum",
                    help="pure-JAX env name for the --algo workload")
    ap.add_argument("--num-envs", type=int, default=8)
    ap.add_argument("--collect-steps", type=int, default=32)
    ap.add_argument("--policy-lag", type=int, default=None,
                    choices=[0, 1],
                    help="overlapped acting engine (repro.rollout."
                    "OverlapEngine): 0 = split collect/update programs, "
                    "serial schedule (bitwise-equal to the fused "
                    "iteration); 1 = pipelined — collect(t+1) is enqueued "
                    "before the host blocks on update(t), acting params "
                    "one update stale; default: serial fused engine "
                    "(incompatible with --fused-epoch at lag 1)")
    ap.add_argument("--chunk-steps", type=int, default=None,
                    help="collect in chunks of this many acting steps, "
                    "folding each chunk into the experience store so "
                    "memory stays bounded at thousands of envs per member "
                    "(must divide --collect-steps; results are bitwise-"
                    "identical to unchunked)")
    ap.add_argument("--updates-per-iter", type=int, default=32,
                    help="chained off-policy updates per fused iteration")
    ap.add_argument("--epochs", type=int, default=4,
                    help="on-policy (ppo) epochs per fused iteration")
    ap.add_argument("--eval-every", type=int, default=2)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--population", type=int, default=1)
    ap.add_argument("--strategy", default="pbt",
                    choices=["pbt", "cem", "none"])
    ap.add_argument("--backend", default="vectorized",
                    choices=["vectorized", "sequential", "sharded",
                             "islands"])
    ap.add_argument("--pbt-interval", type=int, default=50)
    ap.add_argument("--fused-adam", action="store_true",
                    help="hoist every member's Adam step into the "
                    "population-level repro.optim.population_adam "
                    "(kernels/pop_adam on TPU); numerics unchanged")
    ap.add_argument("--fused-linear", action="store_true",
                    help="route population-batched linear layers inside "
                    "the fused update through kernels/pop_matmul "
                    "(implies --fused-adam)")
    ap.add_argument("--fused-epoch", action="store_true",
                    help="run whole train–evolve epochs (pbt_interval "
                    "iterations + evals + evolve) as ONE jitted call; "
                    "needs --steps a multiple of --pbt-interval and "
                    "--eval-every dividing it (bit-exact vs the eager "
                    "loop — tests/test_fused_epoch.py)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint every N steps and at the last one "
                    "(0 = never)")
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--devices", type=int, default=0,
                    help="devices to lay the islands over (0 = all); the "
                    "layout is planned by repro.elastic.plan_layout")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="preferred model-parallel width inside each "
                    "island (islands backend): each member is sharded "
                    "over its island's (data, model) sub-mesh by the "
                    "models/sharding rules — how a 1.6B member fits per "
                    "island")
    ap.add_argument("--resize", default="strict", choices=["strict", "auto"],
                    help="auto: resume a checkpoint whose population size "
                    "differs from --population via elastic re-layout "
                    "(worst members dropped / PBT clones refill)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-dir", default=None, metavar="DIR",
                    help="write structured run telemetry (phase timers, "
                    "per-member fitness/hypers, lineage events, compile "
                    "tracking) as DIR/telemetry.jsonl — tools/report.py "
                    "reconstructs the PBT family tree and timings from it")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace into DIR for "
                    "a bounded window (starts after the warmup iteration)")
    ap.add_argument("--profile-iters", type=int, default=3,
                    help="iterations the --profile trace window spans")
    args = ap.parse_args(argv)

    if (args.arch is None) == (args.algo is None):
        ap.error("pass exactly one of --arch (LM) or --algo (RL)")
    compat.setup_compilation_cache()
    if args.algo is not None:
        return _run_rl(args)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 1), seed=args.seed)
    n = args.population
    print(f"[train] arch={cfg.name} pop={n} strategy={args.strategy} "
          f"backend={args.backend} devices={len(jax.devices())}")

    pcfg = PopulationConfig(
        size=n, strategy=args.strategy, backend=args.backend,
        pbt_interval=args.pbt_interval, donate=False,  # async ckpts read state
        fused_adam=args.fused_adam or args.fused_linear,
        fused_linear=args.fused_linear,
        hyper_space=HyperSpace(
            log_uniform=(("lr_scale", 0.1, 10.0),
                         ("weight_decay", 1e-3, 0.3)),
            uniform=(("warmup_frac", 0.01, 0.25),)))
    layout = None
    if args.backend == "islands":
        from repro.elastic import plan_layout
        layout = plan_layout(args.devices or len(jax.devices()), n,
                             preferred_model=args.model_axis)
        print(f"[train] {layout}")
    telemetry = _telemetry(args, workload="lm", arch=cfg.name)
    trainer = PopTrainer(LMAgent(cfg, tcfg), pcfg, seed=args.seed,
                         layout=layout, checkpoint_dir=args.ckpt_dir,
                         telemetry=telemetry)

    start_step = 0
    if args.resume == "auto":
        meta = trainer._mgr.peek_extra()   # strict: size/fitness guaranteed
        if (args.resize == "auto" and meta is not None
                and meta["size"] != n):
            from repro.elastic import restore_elastic
            with telemetry.compile_scope("resize"):
                resumed, lineage = restore_elastic(trainer)
            print(f"[train] elastic resume from step {resumed}: population "
                  f"{meta['size']} -> {n}, lineage={np.asarray(lineage)}")
        else:
            resumed = trainer.resume()
            if resumed is not None:
                print(f"[train] resumed from step {resumed}")
        if resumed is not None:
            start_step = resumed + 1

    gen = host_batches(cfg.vocab_size, args.batch * n, args.seq_len,
                       seed=args.seed, start_step=start_step)

    def next_batch():
        # phase-timed like the RL branch's collect/update split, so
        # tools/report.py sees where LM wall-clock goes
        with telemetry.phase("data"):
            tokens = jnp.asarray(next(gen))
        if cfg.frontend == "audio_frames":
            batch = {"tokens": tokens,
                     "embeds": jnp.zeros(tokens.shape + (cfg.d_model,),
                                         jnp.dtype(cfg.dtype))}
        elif cfg.frontend == "vision_patches":
            batch = {"tokens": tokens,
                     "patch_embeds": jnp.zeros(
                         (tokens.shape[0], cfg.num_frontend_positions,
                          cfg.d_model), jnp.dtype(cfg.dtype))}
        else:
            batch = {"tokens": tokens}
        return jax.tree.map(
            lambda x: x.reshape((n, args.batch) + x.shape[1:]), batch)

    last = {"loss": float("nan")}
    t0 = time.time()

    def on_step(step, metrics, lineage):
        telemetry.tick_profile(step - start_step, args.profile,
                               iters=args.profile_iters)
        # iteration/evolve rows flow through the telemetry console sink;
        # only the checkpoint cadence (which wants a materialized loss for
        # the extras) stays host-side here
        if args.ckpt_every and ((step + 1) % args.ckpt_every == 0
                                or step == args.steps - 1):
            last["loss"] = float(jnp.mean(metrics["loss"]))
            trainer.save({"loss": last["loss"]})

    metrics = trainer.run(args.steps, lambda step: next_batch(),
                          on_step=on_step)
    trainer.wait()
    if last["loss"] != last["loss"] and metrics is not None:
        last["loss"] = float(jnp.mean(metrics["loss"]))
    telemetry.record("run_end", final_loss=last["loss"],
                     compiles=telemetry.compile_count,
                     compile_secs=round(telemetry.compile_secs, 3))
    telemetry.close()
    print(f"[train] done in {time.time() - t0:.1f}s, "
          f"final loss {last['loss']:.4f}")
    return last["loss"]


if __name__ == "__main__":
    main()
