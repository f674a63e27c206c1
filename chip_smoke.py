"""Bring-up check: the system's main path on a TPU, through its launchers.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips

With no option, in one process (a chip belongs to one process):

  1. device check — exit non-zero unless JAX's first device is a TPU;
  2. PBT-TD3 training, the paper's path at its 256-256 widths, through
     ``repro.launch.train.main`` with the fused population-Adam, fused
     linears and fused train–evolve epochs: fitness finite, a checkpoint
     written, no steady-state compile in the telemetry;
  3. ensemble serving from that checkpoint through
     ``repro.launch.serve.main``: every served action finite, in range and
     of the right shape;
  4. four steps of one full-width qwen2-0.5b member through
     ``repro.launch.train.main``: every loss finite;
  5. the TD3 population update of phase 2, lowered and compiled again:
     ``pop_adam`` and ``pop_matmul`` must be in it as ``tpu_custom_call``s,
     and every linear must have been routed to the kernel;
  6. each of the five Pallas kernels at real widths against its
     ``kernels/ref.py`` oracle run on the host CPU, within the tolerances
     of ``tests/test_kernels.py``.

``--four-chips`` runs only the islands backend over four chips against the
vectorized backend on one, from the same seed, and compares the final
checkpoints member by member, against how far training moved each member.

Details go to earlier lines; the last line of standard output is
``{"ok": true, "device": {...}}``.  Any failed phase exits non-zero before
it.  Telemetry logs go to ``chiprun_out/chip_smoke/``, checkpoints to
``.chip_smoke/``; both are emptied at the start.
"""
import argparse
import functools
import glob
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
CKPT = os.path.join(HERE, ".chip_smoke")

# phase 2: the paper's PBT-TD3 at its published widths (rl/networks.py)
TD3_ARGV = ["--algo", "td3", "--env", "hopper2d", "--population", "8",
            "--batch", "256", "--num-envs", "16", "--collect-steps", "32",
            "--updates-per-iter", "32", "--steps", "8", "--pbt-interval", "4",
            "--eval-every", "2", "--fused-adam", "--fused-linear",
            "--fused-epoch", "--resume", "none"]
# phase 3: serve the trained population as a 4-member ensemble
SERVE_ARGV = ["--algo", "td3", "--env", "hopper2d", "--ensemble", "4",
              "--fused-linear", "--requests", "8", "--poll-every", "0"]
# phase 4: one qwen2-0.5b member at full width (no --smoke), no checkpoint
LM_ARGV = ["--arch", "qwen2_0_5b", "--population", "1", "--strategy", "none",
           "--batch", "4", "--seq-len", "512", "--steps", "4",
           "--ckpt-every", "0", "--resume", "none"]
# --four-chips: islands over four chips against vectorized on one
ISLANDS_ARGV = ["--algo", "td3", "--env", "hopper2d", "--population", "16",
                "--strategy", "none", "--batch", "256", "--num-envs", "16",
                "--collect-steps", "32", "--updates-per-iter", "32",
                "--steps", "2", "--eval-every", "2", "--resume", "none"]
# the largest |islands - vectorized| / |vectorized - init| per member
AGREE = 0.1


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def device_check(count):
    """The first device must be a TPU, and there must be ``count``."""
    import jax
    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu", f"first device is {d.platform!r}, not a TPU")
    check(len(devices) >= count,
          f"{len(devices)} TPU devices, {count} needed")
    say(f"device {d.platform} {d.device_kind} x{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _rows(log_dir):
    with open(os.path.join(log_dir, "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _finite(x):
    return all(math.isfinite(v) for v in _flat(x))


def _flat(x):
    if isinstance(x, (list, tuple)):
        return [v for item in x for v in _flat(item)]
    return [float(x)]


def train_td3(out, ckpt_root, argv=TD3_ARGV):
    from repro.checkpoint import CheckpointManager
    from repro.launch import train
    log_dir = os.path.join(out, "td3_log")
    ckpt_dir = os.path.join(ckpt_root, "td3_ckpt")
    best = train.main(argv + ["--log-dir", log_dir, "--ckpt-dir", ckpt_dir])
    check(math.isfinite(best), f"best fitness {best} is not finite")
    step = CheckpointManager(ckpt_dir).latest()
    check(step is not None, f"no checkpoint in {ckpt_dir}")
    rows = _rows(log_dir)
    compiles = [r for r in rows if r["kind"] == "compile"]
    steady = [r for r in compiles if r["label"] == "steady"]
    check(not steady, f"{len(steady)} steady-state compiles: {steady}")
    fits = [r["fitness"] for r in rows
            if r["kind"] == "members" and r.get("fitness") is not None]
    check(fits and _finite(fits), "member fitness missing or not finite")
    say(f"td3 train: best fitness {best}, {len(fits)} fitness rows, "
        f"checkpoint step {step}, {len(compiles)} compiles, 0 steady")
    return ckpt_dir


def serve_td3(ckpt_dir, argv=SERVE_ARGV):
    import numpy as np
    from repro.envs import make
    from repro.launch import serve
    actions = serve.main(argv + ["--ckpt-dir", ckpt_dir])
    spec = make(argv[argv.index("--env") + 1]).spec
    requests = int(argv[argv.index("--requests") + 1])
    check(actions.ndim == 3 and actions.shape[0] == requests
          and actions.shape[2] == spec.act_dim,
          f"served actions have shape {actions.shape}")
    check(bool(np.all(np.isfinite(actions))), "served actions not finite")
    check(bool(np.all(np.abs(actions) <= spec.act_limit)),
          "served actions outside the action limit")
    say(f"serve: {actions.shape[0]} request batches, actions "
        f"{actions.shape} finite, max |a| {float(np.abs(actions).max())}")


def train_lm(out, ckpt_root, argv=LM_ARGV):
    from repro.launch import train
    log_dir = os.path.join(out, "lm_log")
    final = train.main(argv + ["--log-dir", log_dir, "--ckpt-dir",
                               os.path.join(ckpt_root, "lm_ckpt")])
    steps = int(argv[argv.index("--steps") + 1])
    losses = [r["metrics"]["loss"] for r in _rows(log_dir)
              if r["kind"] == "iter"]
    check(len(losses) == steps, f"{len(losses)} loss rows, {steps} steps")
    check(_finite(losses) and math.isfinite(final),
          f"non-finite loss: {losses}")
    say(f"lm train: {argv[argv.index('--arch') + 1]} {steps} steps, "
        f"losses {losses}")


def kernels_in_update(argv=TD3_ARGV):
    """Lower and compile the population update phase 2 trained with, and
    count what its linears and optimizer compiled to."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import PopulationConfig
    from repro.envs import make
    from repro.pop import PopTrainer
    from repro.rl import get_algo, make_agent
    from repro.rl import networks

    arg = lambda name: argv[argv.index(name) + 1]
    n, b, k = (int(arg("--population")), int(arg("--batch")),
               int(arg("--updates-per-iter")))
    algo, env = get_algo(arg("--algo")), make(arg("--env"))
    pcfg = PopulationConfig(size=n, num_steps=k, hyper_space=algo.hyper_space,
                            donate=False, fused_adam=True, fused_linear=True)
    trainer = PopTrainer(make_agent(algo.name, env.spec), pcfg, seed=0)
    o, a = env.spec.obs_dim, env.spec.act_dim
    batch = {"obs": (k, n, b, o), "action": (k, n, b, a),
             "reward": (k, n, b), "next_obs": (k, n, b, o),
             "done": (k, n, b)}
    batch = {key: jax.ShapeDtypeStruct(shape, jnp.float32)
             for key, shape in batch.items()}

    routes = []
    route = networks._use_pop_matmul

    def spy(fused, x, w):
        use = route(fused, x, w)
        routes.append(use)
        return use

    networks._use_pop_matmul = spy
    try:
        text = trainer._update.lower(trainer.state, batch,
                                     trainer.hypers).compile().as_text()
    finally:
        networks._use_pop_matmul = route
    # kernel instructions are named after the kernel, with a prefix
    # such as jvp_ where they sit under differentiation
    calls = [line.split("=")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    count = {name: sum(name in call for call in calls)
             for name in ("pop_adam", "pop_matmul")}
    say(f"kernels: pop_adam tpu_custom_call {count['pop_adam'] > 0} "
        f"({count['pop_adam']}), pop_matmul tpu_custom_call "
        f"{count['pop_matmul'] > 0} ({count['pop_matmul']}); linears "
        f"traced: {sum(routes)} kernel, {len(routes) - sum(routes)} einsum")
    check(count["pop_adam"] > 0, "pop_adam is not a tpu_custom_call")
    check(count["pop_matmul"] > 0, "pop_matmul is not a tpu_custom_call")
    check(routes and all(routes), "a linear fell back to the einsum")


def _kernel_cases():
    """(name, kernel, reference, inputs, tolerance) at the widths
    ``tests/test_tpu_compile.py`` compiles, with the tolerances of
    ``tests/test_kernels.py``; inputs are drawn as that file draws them."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.envs import make
    from repro.kernels import ops, ref
    from repro.rl import td3

    rng = np.random.default_rng(0)
    f32, bf16 = np.float32, jax.numpy.bfloat16
    normal = lambda *shape: rng.standard_normal(shape).astype(f32)
    tol_f32, tol_bf16 = dict(atol=2e-4, rtol=2e-4), dict(atol=0.15, rtol=0.1)
    cases = []

    spec = make("hopper2d").spec
    shapes = jax.eval_shape(lambda k: td3.init(k, spec.obs_dim, spec.act_dim),
                            jax.random.PRNGKey(0))
    p = sum(x.size for x in jax.tree.leaves((shapes.actor, shapes.critic)))
    for n in (8, 80):
        args = (normal(n, p), normal(n, p), normal(n, p) * 0.1,
                np.abs(normal(n, p)) * 0.01,
                np.linspace(1e-4, 3e-3, n, dtype=f32), np.int32(7))
        cases.append((f"pop_adam N={n} P={p}", ops.pop_adam,
                      ref.pop_adam_ref, args,
                      [dict(atol=1e-5, rtol=1e-7)]
                      + [dict(atol=1e-6, rtol=1e-7)] * 2))

    n, b = 8, 256
    for k in (14, 256):
        for m in (256, 1):
            args = (normal(n, b, k), normal(n, k, m) / np.sqrt(k),
                    normal(n, m))
            cases.append((f"pop_matmul N={n} B={b} K={k} M={m}",
                          functools.partial(ops.pop_matmul,
                                            activation="relu"),
                          functools.partial(ref.pop_matmul_ref,
                                            activation="relu"),
                          args, [tol_f32]))

    cfg = get_config("qwen2_0_5b")
    s, h, hkv, d = 2048, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    args = tuple(normal(1, heads, s, d).astype(bf16)
                 for heads in (h, hkv, hkv))
    cases.append((f"flash_attention H={h} Hkv={hkv} S={s} D={d} bf16",
                  ops.flash_attention, ref.flash_attention_ref, args,
                  [tol_bf16]))

    cfg = get_config("rwkv6_1_6b")
    h, d, s, chunk = cfg.num_heads, cfg.ssm_head_dim, 512, cfg.ssm_chunk
    args = (normal(1, h, s, d), normal(1, h, s, d), normal(1, h, s, d),
            -np.exp(normal(1, h, s, d) * 0.5 - 2.0), normal(h, d) * 0.3,
            normal(1, h, d, d) * 0.1)
    cases.append((f"wkv6 H={h} D={d} S={s} chunk={chunk}",
                  functools.partial(ops.wkv6, chunk=chunk), ref.wkv6_ref,
                  args, [tol_f32] * 2))

    cfg = get_config("zamba2_7b")
    pd, nd, s, h, chunk = cfg.ssm_head_dim, cfg.ssm_state, 1024, 8, \
        cfg.ssm_chunk
    args = (normal(1, s, h, pd), np.log1p(np.exp(normal(1, s, h))),
            -np.exp(normal(h) * 0.3), normal(1, s, nd), normal(1, s, nd),
            normal(1, h, pd, nd) * 0.1)
    cases.append((f"ssd H={h} P={pd} N={nd} S={s} chunk={chunk}",
                  functools.partial(ops.ssd, chunk=chunk), ref.ssd_ref,
                  args, [tol_f32] * 2))
    return cases


def kernels_against_reference():
    """Each Pallas kernel on the chip against its ``kernels/ref.py`` oracle
    run on the host CPU, in full float32."""
    import jax
    import numpy as np

    cpu = jax.devices("cpu")[0]
    failed = []
    for name, kernel, reference, args, tols in _kernel_cases():
        got = kernel(*args)
        want = jax.jit(reference)(*jax.device_put(args, cpu))
        got, want = (x if isinstance(x, tuple) else (x,)
                     for x in (got, want))
        check(len(got) == len(want) == len(tols),
              f"{name}: {len(got)} outputs, {len(want)} expected")
        errs, worst = [], []
        for g, w, tol in zip(got, want, tols):
            g, w = (np.asarray(x, np.float64) for x in (g, w))
            check(g.shape == w.shape, f"{name}: shape {g.shape} != {w.shape}")
            err = np.abs(g - w)
            errs.append(float(err.max()))
            # 1.0 is the tolerance's edge
            worst.append(float(np.max(
                err / (tol["atol"] + tol["rtol"] * np.abs(w)))))
            if worst[-1] > 1.0:
                failed.append(name)
        say(f"kernel {name}: on {jax.devices()[0].platform}, max abs err "
            f"{errs}, worst err / tolerance {worst} (atol "
            f"{[t['atol'] for t in tols]}, rtol {[t['rtol'] for t in tols]})")
    check(not failed, f"kernels off their reference: {failed}")


def _initial_state(argv):
    """The checkpointed tree's leaves before any training: the trainer
    ``repro.launch.train`` builds for ``argv``, on one device."""
    import jax
    import numpy as np
    from repro.configs.base import PopulationConfig
    from repro.envs import make
    from repro.pop import PopTrainer
    from repro.rl import get_algo, make_agent

    arg = lambda name: argv[argv.index(name) + 1]
    algo, env = get_algo(arg("--algo")), make(arg("--env"))
    pcfg = PopulationConfig(size=int(arg("--population")),
                            strategy=arg("--strategy"),
                            num_steps=int(arg("--updates-per-iter")),
                            hyper_space=algo.hyper_space, donate=False)
    trainer = PopTrainer(make_agent(algo.name, env.spec), pcfg, seed=0)
    tree = (trainer.state, trainer.strategy.export_state())
    return {f"leaf_{i}": np.asarray(x)
            for i, x in enumerate(jax.tree.leaves(tree))}


def islands_against_vectorized(ckpt_root, argv=ISLANDS_ARGV, devices=4):
    """TD3 from one seed on the islands backend over ``devices`` chips and
    on the vectorized backend on one; the final checkpoints must agree.

    The two programs round differently on a TPU, and training amplifies
    it, so agreement is measured against what training changed: for each
    member, the distance between its islands and vectorized states must be
    a small fraction of the distance its vectorized state moved from
    initialization.  A member trained on another member's data, with
    another member's hyperparameters, or not trained at all, is about as
    far from its counterpart as training moved it."""
    import numpy as np
    from repro.launch import train

    ckpts = {}
    for backend in ("islands", "vectorized"):
        extra = ["--backend", backend]
        if backend == "islands":
            extra += ["--devices", str(devices)]
        ckpt = ckpts[backend] = os.path.join(ckpt_root, f"{backend}_ckpt")
        best = train.main(argv + extra + ["--ckpt-dir", ckpt])
        check(math.isfinite(best), f"{backend}: best fitness {best}")
        say(f"{backend}: best fitness {best}")

    def load(backend):
        """The last checkpoint: main tree and aux trees, leaves by name."""
        (step,) = sorted(glob.glob(os.path.join(ckpts[backend],
                                                "step_*")))[-1:]
        out = {}
        for path in glob.glob(os.path.join(step, "*.npz")):
            with np.load(path) as z:
                name = os.path.basename(path)[:-len(".npz")]
                out.update({f"{name}/{k}": z[k] for k in z.files})
        return out

    isl, vec = load("islands"), load("vectorized")
    check(isl.keys() == vec.keys(), "checkpoints differ in structure")
    hypers = [k for k in isl if k.startswith("aux_hypers/")]
    check(all(np.array_equal(isl[k], vec[k]) for k in hypers),
          "islands and vectorized hypers differ")
    init = {f"arrays/{k}": v for k, v in _initial_state(argv).items()}
    check(init.keys() == {k for k in isl if k.startswith("arrays/")},
          "checkpoint and initial state differ in structure")
    isl, vec = ({k: t[k] for k in init} for t in (isl, vec))
    diff = max(float(np.max(np.abs(isl[k].astype(np.float64)
                                   - vec[k].astype(np.float64))))
               if isl[k].size else 0.0 for k in isl)
    n = int(argv[argv.index("--population") + 1])
    per = [k for k in sorted(isl) if isl[k].ndim and isl[k].shape[0] == n
           and np.issubdtype(isl[k].dtype, np.floating)]
    a, b, c = (np.concatenate([t[k].reshape(n, -1).astype(np.float64)
                               for k in per], axis=1)
               for t in (isl, vec, init))
    apart = np.linalg.norm(a - b, axis=1)
    moved = np.linalg.norm(b - c, axis=1)
    ratio = apart / moved
    say(f"islands x{devices} vs vectorized x1: {len(isl)} leaves, "
        f"max abs diff {diff}; per member over {a.shape[1]} values, "
        f"|islands - vectorized| / |vectorized - init|: max "
        f"{float(ratio.max())}, median {float(np.median(ratio))}; "
        f"|vectorized - init| min {float(moved.min())}, "
        f"max {float(moved.max())}")
    check(np.all(moved > 0), "a member did not move from initialization")
    check(np.all(ratio <= AGREE),
          f"islands members are not their vectorized counterparts: "
          f"ratios {ratio.tolist()}, bound {AGREE}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only islands over four chips against "
                    "vectorized on one")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    try:
        device = device_check(count)
        from repro import compat
        compat.setup_compilation_cache()
        for d in (OUT, CKPT):
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d)
        if args.four_chips:
            islands_against_vectorized(CKPT)
        else:
            ckpt = train_td3(OUT, CKPT)
            serve_td3(ckpt)
            train_lm(OUT, CKPT)
            kernels_in_update()
            kernels_against_reference()
    except PhaseFailed as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
