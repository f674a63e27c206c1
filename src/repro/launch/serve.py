"""Serving driver: both inference workloads behind one CLI.

  * ``--arch <id>``   — LM batched decode: prefill + jit'd decode loop with
                        a KV cache (one compiled step reused every token —
                        the inference analogue of the paper's compilation
                        protocol).
  * ``--algo <name>`` — population-as-ensemble RL serving: load any
                        checkpoint ``launch/train.py`` produced, promote a
                        fitness+diversity serving set
                        (``repro.serve.ContinuousEvaluator``), and answer
                        batched observation requests through the
                        ``BatchServer``'s single jitted ensemble call —
                        continuously re-polling the checkpoint dir so a
                        still-training population keeps refreshing the
                        ensemble it serves.

``python -m repro.launch.serve --arch qwen2-0.5b --smoke --tokens 32``
``python -m repro.launch.serve --algo td3 --ckpt-dir /tmp/repro_ckpt``

Both launchers share jax's persistent compilation cache
(``repro.compat.setup_compilation_cache``: ``$JAX_COMPILATION_CACHE_DIR``,
else ``<repo>/.jax_cache``), so serving restarts skip cold XLA compiles.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs import get_config
from repro.models import lm as lm_mod
from repro.telemetry import make_telemetry


def generate(cfg, params, prompt_tokens, *, steps: int, max_len: int,
             extra_inputs=None, greedy: bool = True, key=None):
    b, s0 = prompt_tokens.shape
    serve = jax.jit(lm_mod.make_serve_step(cfg))
    state = lm_mod.init_decode_state(cfg, b, max_len)

    # prefill token-by-token through the same compiled step (keeps one
    # executable; a chunked prefill kernel is the production variant)
    tok = prompt_tokens[:, :1]
    out = [tok]
    logits = None
    for t in range(s0 + steps - 1):
        batch = {"tokens": tok}
        if cfg.frontend == "audio_frames":
            batch["embeds"] = jnp.zeros((b, 1, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        logits, state = serve(params, batch, state, jnp.asarray(t, jnp.int32))
        if t + 1 < s0:
            tok = prompt_tokens[:, t + 1:t + 2]
        else:
            if greedy:
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            else:
                key, ks = jax.random.split(key)
                tok = jax.random.categorical(ks, logits[:, -1])[:, None]
            out.append(tok)
    return jnp.concatenate(out, axis=1)


def _serve_rl(args):
    """RL branch: ensemble inference over a trained population.

    Requests are synthesized from env resets (the env is the traffic
    model this box has); a real frontend swaps :func:`_request_batch` for
    its socket and keeps everything else.  Returns the served actions,
    ``(requests, batch, act_dim)``.
    """
    from repro.checkpoint import CheckpointManager
    from repro.envs import make
    from repro.rl import make_agent
    from repro.serve import (BatchServer, ContinuousEvaluator, PolicyForward,
                             probe_observations)

    env = make(args.env)
    agent = make_agent(args.algo, env.spec)
    # --fused-linear: the ensemble call evaluates all members through the
    # population-batched forward (kernels/pop_matmul layout) instead of
    # vmap of the per-member apply — same actions, one kernel on TPU
    forward = PolicyForward.fused_for_agent(agent) if args.fused_linear \
        else None
    telemetry = make_telemetry(
        args.log_dir, console=False,
        meta={"workload": "serve-rl", "algo": args.algo, "env": args.env,
              "mode": args.mode, "ensemble": args.ensemble,
              "batch": args.batch})
    mgr = CheckpointManager(args.ckpt_dir)
    if mgr.latest() is None:
        raise FileNotFoundError(
            f"no checkpoint in {args.ckpt_dir}; train one first: "
            f"python -m repro.launch.train --algo {args.algo} "
            f"--env {args.env} --ckpt-dir {args.ckpt_dir}")

    key = jax.random.PRNGKey(args.seed)
    key, kp = jax.random.split(key)
    watcher = ContinuousEvaluator(
        mgr, agent, size=args.ensemble,
        probe_obs=probe_observations(env, kp, args.probe),
        diversity_weight=args.diversity_weight, forward=forward,
        telemetry=telemetry)
    sset = watcher.poll()

    mesh = None
    if args.islands:
        from repro.elastic import plan_layout
        mesh = plan_layout(len(jax.devices()), sset.size).mesh
        print(f"[serve] islands mesh over {len(jax.devices())} devices")
    server = BatchServer(watcher.forward, env.spec, sset,
                         max_batch=args.batch, mode=args.mode, mesh=mesh,
                         telemetry=telemetry,
                         telemetry_every=args.telemetry_every)
    print(f"[serve] algo={args.algo} env={args.env} mode={args.mode} "
          f"batch={args.batch} {sset.describe()}")

    def _request_batch(k):
        _, obs = jax.vmap(env.reset)(jax.random.split(k, args.batch))
        return np.asarray(obs)

    # warm-up compiles the ensemble executable outside the timed loop
    server.warmup()
    server.serve(_request_batch(key))

    lat, served_actions = [], []
    t0 = time.time()
    for i in range(args.requests):
        telemetry.tick_profile(i, args.profile, iters=args.profile_iters)
        key, kr = jax.random.split(key)
        obs = _request_batch(kr)
        t1 = time.perf_counter()
        actions = server.serve(obs)
        lat.append(time.perf_counter() - t1)
        served_actions.append(actions)
        if args.poll_every and (i + 1) % args.poll_every == 0:
            # a promotion of a new ensemble SIZE recompiles the serving
            # executable once — attribute those compile rows to it
            with telemetry.compile_scope("promotion"):
                newer = watcher.poll(server)
            if newer is not None:
                ev = watcher.events[-1]
                print(f"[serve] promoted step {newer.step}: "
                      f"+{ev['promoted']} -{ev['demoted']}")
    dt = time.time() - t0
    served = args.requests * args.batch
    lat_ms = 1e3 * np.asarray(lat)
    print(f"[serve] {served} requests in {dt:.2f}s "
          f"({served / dt:.0f} req/s, p50 {np.percentile(lat_ms, 50):.2f} ms"
          f" p99 {np.percentile(lat_ms, 99):.2f} ms per batch)")
    print(f"[serve] last actions[:2] = {np.asarray(actions)[:2]}")
    server.report_telemetry()            # flush the partial tail window
    telemetry.record("run_end", requests=served, secs=round(dt, 4),
                     req_per_s=round(served / dt, 2),
                     compiles=telemetry.compile_count,
                     compile_secs=round(telemetry.compile_secs, 4))
    telemetry.close()
    return np.stack([np.asarray(a) for a in served_actions])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM config id (decode workload; exclusive with "
                    "--algo)")
    ap.add_argument("--algo", default=None,
                    help="RL algorithm whose launch/train.py checkpoint to "
                    "serve as an ensemble (exclusive with --arch)")
    ap.add_argument("--env", default="pendulum",
                    help="pure-JAX env of the trained checkpoint")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt",
                    help="checkpoint dir written by launch/train.py")
    ap.add_argument("--ensemble", type=int, default=4,
                    help="serving-set size (fitness + DvD selection)")
    ap.add_argument("--mode", default="mean",
                    choices=["mean", "vote", "best"],
                    help="ensemble reduction")
    ap.add_argument("--requests", type=int, default=64,
                    help="request batches to serve in the demo loop")
    ap.add_argument("--poll-every", type=int, default=16,
                    help="re-poll the checkpoint dir every N batches "
                    "(0 = never): continuous promotion")
    ap.add_argument("--probe", type=int, default=32,
                    help="probe observations for behavioral embeddings")
    ap.add_argument("--diversity-weight", type=float, default=1.0)
    ap.add_argument("--fused-linear", action="store_true",
                    help="serve the ensemble through the population-"
                    "batched forward (kernels/pop_matmul on TPU) instead "
                    "of vmap over members")
    ap.add_argument("--islands", action="store_true",
                    help="shard the ensemble's member axis over all "
                    "devices (populations too big for one accelerator)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-dir", default=None, metavar="DIR",
                    help="write structured telemetry (latency histogram, "
                    "promotion audit trail, compile events) to "
                    "DIR/telemetry.jsonl; inspect with tools/report.py")
    ap.add_argument("--telemetry-every", type=int, default=16,
                    help="summarize the serving latency window into one "
                    "telemetry row every N served batches")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of a few steady-"
                    "state request batches into DIR")
    ap.add_argument("--profile-iters", type=int, default=3,
                    help="request batches to keep the profiler trace open")
    args = ap.parse_args(argv)

    if (args.arch is None) == (args.algo is None):
        ap.error("pass exactly one of --arch (LM) or --algo (RL ensemble)")
    compat.setup_compilation_cache()
    if args.algo is not None:
        return _serve_rl(args)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    telemetry = make_telemetry(
        args.log_dir, console=False,
        meta={"workload": "serve-lm", "arch": cfg.name,
              "batch": args.batch, "tokens": args.tokens})
    key = jax.random.PRNGKey(args.seed)
    params = lm_mod.init_params(key, cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    if args.profile:
        telemetry.start_profile(args.profile)
    t0 = time.time()
    out = generate(cfg, params, prompts, steps=args.tokens,
                   max_len=args.prompt_len + args.tokens + 1, key=key,
                   greedy=False)
    dt = time.time() - t0
    telemetry.stop_profile()
    n_new = args.batch * args.tokens
    print(f"[serve] arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({1e3 * dt / n_new:.2f} ms/token)")
    print(out[:2])
    telemetry.record("run_end", tokens=n_new, secs=round(dt, 4),
                     ms_per_token=round(1e3 * dt / n_new, 4),
                     compiles=telemetry.compile_count,
                     compile_secs=round(telemetry.compile_secs, 4))
    telemetry.close()
    return out


if __name__ == "__main__":
    main()
