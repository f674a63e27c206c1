"""Plain reference for ``pbt_td3_hopper2d``: PBT over TD3 members acting
in the planar hopper, written from the equations in plain ``jax.numpy``
at float32 with every matmul at ``Precision.HIGHEST``.  It imports nothing
of the program.

What it computes, per fused train-evolve epoch (Flajolet et al. 2022,
section 5.1; Fujimoto et al. 2018):

* acting: each member steps its envs with ``clip(actor(obs) + sigma eps)``
  (``sigma`` its ``explore_noise``), the hopper integrated by
  semi-implicit Euler with spring-damper joints and penalty contacts, a
  time limit of ``episode_length`` and auto-reset;
* a FIFO replay per member, uniform samples with replacement;
* ``updates_per_iter`` chained TD3 updates once every replay holds a
  batch: clipped double-Q targets with smoothed target actions, Adam on
  both critics, the actor every ``floor(step f)`` change (``f`` its
  ``policy_freq``), Polyak targets;
* every ``eval_every`` iterations, deterministic episodes of
  ``episode_length`` steps on ``eval_envs`` fresh envs, first-episode
  return as fitness;
* PBT at the end of the epoch: the bottom ``exploit_frac`` copy a random
  top member's whole state and hyperparameters, then resample or perturb
  the hyperparameters.

The random draws follow the same split chain from the same keys as the
system under test (they are inputs, like the weights), so the two see the
same batches and noise.  ``inputs`` makes the weights, hyperparameters,
env states and keys from the seed; the harness hands the same to both.

``dtype="bfloat16"`` computes the networks in bfloat16 (the control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from record import change_norms, rms_grad_norms

HI = jax.lax.Precision.HIGHEST


def root_key(seed: int):
    """A raw threefry key from a seed of any size."""
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jnp.asarray(words, jnp.uint32)


# ------------------------------------------------------------------ hopper
def _rot(th, lx, lz):
    c, s = jnp.cos(th), jnp.sin(th)
    return jnp.stack([c * lx - s * lz, s * lx + c * lz], -1)


def _point_vel(vel, om, r):
    return vel + om[..., None] * jnp.stack([-r[..., 1], r[..., 0]], -1)


def _cross2(r, f):
    return r[..., 0] * f[..., 1] - r[..., 1] * f[..., 0]


def _forces(h, pos, th, vel, om, a):
    m = jnp.asarray(h["mass"])
    f = jnp.zeros((4, 2)).at[:, 1].add(-h["gravity"] * m)
    tau = jnp.zeros((4,))
    for j, (p, ra, c, rb, lo, hi) in enumerate(h["joints"]):
        wa, wb = _rot(th[p], *ra), _rot(th[c], *rb)
        dx = (pos[p] + wa) - (pos[c] + wb)
        dv = _point_vel(vel[p], om[p], wa) - _point_vel(vel[c], om[c], wb)
        fj = h["joint_k"] * dx + h["joint_c"] * dv
        f = f.at[c].add(fj).at[p].add(-fj)
        tau = tau.at[c].add(_cross2(wb, fj)).at[p].add(_cross2(wa, -fj))
        rel = th[c] - th[p]
        tj = (h["torque"][j] * a[j] - h["rot_c"] * (om[c] - om[p])
              - h["limit_k"] * (jnp.maximum(rel - hi, 0.0)
                                + jnp.minimum(rel - lo, 0.0)))
        tau = tau.at[c].add(tj).at[p].add(-tj)
    for b, off in h["contacts"]:
        r = _rot(th[b], *off)
        p_w = pos[b] + r
        v_w = _point_vel(vel[b], om[b], r)
        pen = jnp.maximum(-p_w[1], 0.0)
        active = (pen > 0.0).astype(jnp.float32)
        fn = jnp.maximum(h["contact_k"] * pen - h["contact_c"] * v_w[1],
                         0.0) * active
        ft = -h["friction"] * fn * jnp.tanh(v_w[0] / h["v_smooth"])
        fc = jnp.stack([ft, fn], -1)
        f = f.at[b].add(fc)
        tau = tau.at[b].add(_cross2(r, fc))
    return f, tau


def _observe(s):
    th, om = s["th"], s["om"]
    return jnp.concatenate([
        jnp.stack([s["pos"][0, 1], th[0], th[1] - th[0], th[2] - th[1],
                   th[3] - th[2]]),
        s["vel"][0],
        jnp.stack([om[0], om[1] - om[0], om[2] - om[1], om[3] - om[2]])])


def _reset(h, key):
    k1, k2, k3 = jax.random.split(key, 3)
    n = h["reset_noise"]
    s = {"pos": jnp.asarray(h["rest_pos"])
         + jax.random.uniform(k1, (4, 2), minval=-n, maxval=n),
         "th": jax.random.uniform(k2, (4,), minval=-n, maxval=n),
         "vel": jnp.zeros((4, 2)), "om": jnp.zeros((4,)),
         "t": jnp.zeros((), jnp.int32), "key": k3}
    return s, _observe(s)


def _raw_step(h, s, action):
    a = jnp.clip(action, -1.0, 1.0)
    m = jnp.asarray(h["mass"])
    inertia = m * jnp.asarray(h["length"]) ** 2 / 12.0
    dt = h["dt"]

    def substep(carry, _):
        pos, th, vel, om = carry
        f, tau = _forces(h, pos, th, vel, om, a)
        vel = vel + dt * f / m[:, None]
        om = om + dt * tau / inertia
        return (pos + dt * vel, th + dt * om, vel, om), None

    (pos, th, vel, om), _ = jax.lax.scan(
        substep, (s["pos"], s["th"], s["vel"], s["om"]), None,
        length=h["substeps"])
    fwd = (pos[0, 0] - s["pos"][0, 0]) / (dt * h["substeps"])
    reward = fwd + 1.0 - 1e-3 * jnp.sum(a ** 2)
    new = dict(s, pos=pos, th=th, vel=vel, om=om, t=s["t"] + 1)
    term = (pos[0, 1] < h["z_min"]) | (jnp.abs(th[0]) > h["th_max"])
    return new, _observe(new), reward, term


def env_step(h, s, action):
    """Time limit and auto-reset: returns the pre-reset observation, and a
    state that starts a fresh episode where this one ended."""
    new, obs, reward, term = _raw_step(h, s, action)
    trunc = ~term & (new["t"] >= h["episode_length"])
    done = term | trunc
    k_next, k_reset = jax.random.split(new["key"])
    fresh, _ = _reset(h, k_reset)
    fresh = dict(fresh, key=k_next)
    new = dict(new, key=k_next)
    state = jax.tree.map(lambda x, y: jnp.where(done, x, y), fresh, new)
    return state, obs, reward, done, trunc


# ---------------------------------------------------------------- networks
def _linear(p, x, dtype):
    y = jnp.dot(x.astype(dtype), p["w"].astype(dtype), precision=HI,
                preferred_element_type=dtype)
    return y + p["b"].astype(dtype)


def mlp(p, x, dtype, final=None):
    n = len(p)
    for i in range(n):
        x = _linear(p[f"layer_{i}"], x, dtype)
        if i < n - 1:
            x = jax.nn.relu(x)
    if final == "tanh":
        x = jnp.tanh(x)
    return x.astype(jnp.float32)


def actor_fwd(p, obs, dtype=jnp.float32):
    return mlp(p, obs, dtype, final="tanh")


def critic_fwd(p, obs, act, dtype=jnp.float32):
    x = jnp.concatenate([obs, act], -1)
    return mlp(p["q1"], x, dtype)[..., 0], mlp(p["q2"], x, dtype)[..., 0]


def _mlp_init(key, sizes):
    out = {}
    for i, k in enumerate(jax.random.split(key, len(sizes) - 1)):
        kw, kb = jax.random.split(k)
        a, b = sizes[i], sizes[i + 1]
        out[f"layer_{i}"] = {
            "w": jax.random.normal(kw, (a, b)) / math.sqrt(a),
            "b": 0.01 * jax.random.normal(kb, (b,))}
    return out


def hyper_bounds(cfg):
    space = cfg["hyper_space"]
    return {name: (lo, hi)
            for name, lo, hi in space["log_uniform"] + space["uniform"]}


def sample_hypers(key, space, n):
    out = {}
    for i, (name, lo, hi) in enumerate(space["log_uniform"]):
        out[name] = jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, i), (n,), minval=jnp.log(lo),
            maxval=jnp.log(hi)))
    for j, (name, lo, hi) in enumerate(space["uniform"]):
        out[name] = jax.random.uniform(jax.random.fold_in(key, 1000 + j),
                                       (n,), minval=lo, maxval=hi)
    return out


def inputs(cfg, traffic, seed):
    """Everything a run starts from, made from the seed in one jitted
    call: stacked actor and critic weights, per-member hyperparameters,
    per-member update keys, env states and the epoch key."""
    n, e = traffic["population"], traffic["num_envs"]
    obs, act, hidden = cfg["obs_dim"], cfg["act_dim"], list(cfg["hidden"])
    h = cfg["hopper2d"]

    @jax.jit
    def make(key):
        ka, kc, kh, ke, km, kt = jax.random.split(key, 6)
        actor = jax.vmap(lambda k: _mlp_init(k, [obs, *hidden, act]))(
            jax.random.split(ka, n))

        def critic_one(k):
            k1, k2 = jax.random.split(k)
            return {"q1": _mlp_init(k1, [obs + act, *hidden, 1]),
                    "q2": _mlp_init(k2, [obs + act, *hidden, 1])}

        critic = jax.vmap(critic_one)(jax.random.split(kc, n))
        hypers = sample_hypers(kh, cfg["hyper_space"], n)
        keys = jax.random.split(ke, n * e).reshape(n, e, -1)
        env_state, env_obs = jax.vmap(jax.vmap(lambda k: _reset(h, k)))(keys)
        return {"actor": actor, "critic": critic, "hypers": hypers,
                "env_state": env_state, "obs": env_obs,
                "member_keys": jax.random.split(km, n), "epoch_key": kt}

    return make(root_key(seed))


# ------------------------------------------------------------------- adam
def _adam(cfg, params, grads, opt, lr, mask=None):
    """Per-member Adam: ``lr`` and the step count ``opt["t"]`` are (N,)."""
    b1, b2, eps = (cfg["adam"][k] for k in ("b1", "b2", "eps"))
    t = opt["t"] + 1
    col = lambda v, x: v.reshape(v.shape + (1,) * (x.ndim - 1))
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)
    c1, c2 = 1 - b1 ** t.astype(jnp.float32), 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - col(lr, p) * (m / col(c1, p))
        / (jnp.sqrt(v / col(c2, p)) + eps), params, mu, nu)
    opt_new = {"mu": mu, "nu": nu, "t": t}
    if mask is None:
        return new, opt_new
    sel = lambda a, b: jax.tree.map(
        lambda x, y: jnp.where(col(mask, x), x, y), a, b)
    return sel(new, params), sel(opt_new, opt)


def _zeros_opt(params, n):
    z = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"mu": z(), "nu": z(), "t": jnp.zeros((n,), jnp.int32)}


class Reference:
    """The epoch loop of the configuration, from :func:`inputs`."""

    def __init__(self, cfg, traffic, seed, *, dtype="float32"):
        self.cfg, self.traffic = cfg, traffic
        self.dtype = jnp.dtype(dtype)
        self.h = cfg["hopper2d"]
        self.n = traffic["population"]
        inp = inputs(cfg, traffic, seed)
        self.init_params = {"actor": inp["actor"], "critic": inp["critic"]}
        n = self.n
        self.state = {
            "actor": inp["actor"], "critic": inp["critic"],
            "target_actor": inp["actor"], "target_critic": inp["critic"],
            "actor_opt": _zeros_opt(inp["actor"], n),
            "critic_opt": _zeros_opt(inp["critic"], n),
            "step": jnp.zeros((n,), jnp.int32), "key": inp["member_keys"]}
        self.hypers = inp["hypers"]
        self.env_state, self.obs = inp["env_state"], inp["obs"]
        self.key = inp["epoch_key"]
        steps = traffic["check_steps"] * traffic["pbt_interval"] \
            * traffic["collect_steps"] * traffic["num_envs"]
        self.capacity = min(cfg["replay_capacity"], steps)
        spec = {"obs": (cfg["obs_dim"],), "action": (cfg["act_dim"],),
                "reward": (), "next_obs": (cfg["obs_dim"],), "done": ()}
        self.buf = {k: jnp.zeros((n, self.capacity) + s)
                    for k, s in spec.items()}
        self.total = 0
        self._collect = jax.jit(self._collect_fn)
        self._updates = jax.jit(self._updates_fn)
        self._evaluate = jax.jit(self._evaluate_fn)
        self._evolve = jax.jit(self._evolve_fn)

    # acting -----------------------------------------------------------
    def _collect_fn(self, actor, env_state, obs, buf, pos, key, explore):
        h, t = self.h, self.traffic["collect_steps"]
        e = self.traffic["num_envs"]

        def member(actor, env_state, obs, key, sigma):
            def body(carry, _):
                env_state, obs, k = carry
                k, ka = jax.random.split(k)
                a = actor_fwd(actor, obs, self.dtype)
                a = jnp.clip(a + sigma * jax.random.normal(ka, a.shape),
                             -1.0, 1.0)
                env_state, tobs, r, done, trunc = jax.vmap(
                    lambda s, x: env_step(h, s, x))(env_state, a)
                nobs = jax.vmap(_observe)(env_state)
                tr = {"obs": obs, "action": a, "reward": r, "next_obs": tobs,
                      "done": (done & ~trunc).astype(jnp.float32)}
                return (env_state, nobs, k), tr

            (env_state, obs, _), traj = jax.lax.scan(
                body, (env_state, obs, key), None, length=t)
            traj = jax.tree.map(lambda x: x.reshape((t * e,) + x.shape[2:]),
                                traj)
            return env_state, obs, traj

        env_state, obs, traj = jax.vmap(member)(
            actor, env_state, obs, jax.random.split(key, self.n), explore)
        idx = (pos + jnp.arange(t * e)) % self.capacity
        buf = {k: buf[k].at[:, idx].set(traj[k]) for k in buf}
        return env_state, obs, buf

    # updates ----------------------------------------------------------
    def _update(self, state, batch, hyp):
        cfg, dt = self.cfg, self.dtype
        ks = jax.vmap(jax.random.split)(state["key"])
        key, kc = ks[:, 0], ks[:, 1]
        b = batch["action"].shape[1]
        eps = jax.vmap(lambda k: jax.random.normal(
            k, (b, cfg["act_dim"])))(kc)

        def closs(critic, t_actor, t_critic, bt, e, hn, hd):
            noise = jnp.clip(hn * e, -cfg["noise_clip"], cfg["noise_clip"])
            na = jnp.clip(actor_fwd(t_actor, bt["next_obs"], dt) + noise,
                          -1.0, 1.0)
            tq1, tq2 = critic_fwd(t_critic, bt["next_obs"], na, dt)
            target = jax.lax.stop_gradient(
                bt["reward"] + hd * (1 - bt["done"]) * jnp.minimum(tq1, tq2))
            q1, q2 = critic_fwd(critic, bt["obs"], bt["action"], dt)
            return jnp.mean((q1 - target) ** 2) + jnp.mean((q2 - target) ** 2)

        def aloss(actor, critic, bt):
            q1, _ = critic_fwd(critic, bt["obs"], actor_fwd(actor, bt["obs"],
                                                            dt), dt)
            return -jnp.mean(q1)

        cl, cg = jax.vmap(jax.value_and_grad(closs))(
            state["critic"], state["target_actor"], state["target_critic"],
            batch, eps, hyp["noise"], hyp["discount"])
        critic, critic_opt = _adam(cfg, state["critic"], cg,
                                   state["critic_opt"], hyp["critic_lr"])
        f = hyp["policy_freq"]
        s = state["step"].astype(jnp.float32)
        do_actor = jnp.floor((s + 1) * f) > jnp.floor(s * f)
        _, ag = jax.vmap(jax.value_and_grad(aloss))(state["actor"], critic,
                                                    batch)
        actor, actor_opt = _adam(cfg, state["actor"], ag, state["actor_opt"],
                                 hyp["actor_lr"], mask=do_actor)
        tau = cfg["tau"]
        soft = lambda t, o: jax.tree.map(lambda x, y: (1 - tau) * x + tau * y,
                                         t, o)
        col = lambda x: do_actor.reshape((-1,) + (1,) * (x.ndim - 1))
        target_actor = jax.tree.map(
            lambda new, old: jnp.where(col(new), new, old),
            soft(state["target_actor"], actor), state["target_actor"])
        new = dict(state, actor=actor, critic=critic, actor_opt=actor_opt,
                   critic_opt=critic_opt, target_actor=target_actor,
                   target_critic=soft(state["target_critic"], critic),
                   step=state["step"] + 1, key=key)
        return new, cl

    def _updates_fn(self, state, buf, total, key, hyp):
        k_upd, n = self.traffic["updates_per_iter"], self.n
        bsz = self.cfg["batch_size"]
        keys = jax.random.split(key, k_upd * n).reshape(k_upd, n, -1)
        limit = jnp.maximum(jnp.minimum(total, self.cfg["replay_capacity"]),
                            1)

        def sample(mbuf, k):
            idx = jax.random.randint(k, (bsz,), 0, limit)
            return {name: x[idx] for name, x in mbuf.items()}

        batches = jax.vmap(jax.vmap(sample), in_axes=(None, 0))(buf, keys)

        def body(state, batch):
            return self._update(state, batch, hyp)

        state, losses = jax.lax.scan(body, state, batches)
        return state, jnp.mean(losses, axis=0)

    # evaluation and evolution -----------------------------------------
    def _evaluate_fn(self, actor, key):
        h, e = self.h, self.traffic["eval_envs"]

        def member(actor, key):
            s, obs = jax.vmap(lambda k: _reset(h, k))(jax.random.split(key, e))

            def body(carry, _):
                s, obs, ret, alive = carry
                a = actor_fwd(actor, obs, self.dtype)
                s, _, r, done, _ = jax.vmap(
                    lambda st, x: env_step(h, st, x))(s, a)
                ret = ret + r * alive
                alive = alive * (1.0 - done.astype(jnp.float32))
                return (s, jax.vmap(_observe)(s), ret, alive), None

            (_, _, ret, _), _ = jax.lax.scan(
                body, (s, obs, jnp.zeros((e,)), jnp.ones((e,))), None,
                length=h["episode_length"])
            return ret.mean()

        return jax.vmap(member)(actor, jax.random.split(key, self.n))

    def _evolve_fn(self, key, state, hypers, fitness):
        p = self.cfg["pbt"]
        n = fitness.shape[0]
        k = max(1, int(round(n * p["exploit_frac"])))
        order = jnp.argsort(fitness)
        bottom, top = order[:k], order[n - k:]
        kp, kh = jax.random.split(key)
        parents = jnp.arange(n).at[bottom].set(
            top[jax.random.randint(kp, (k,), 0, k)])
        state = jax.tree.map(lambda x: x[parents], state)
        hypers = {name: x[parents] for name, x in hypers.items()}
        mask = jnp.zeros((n,), bool).at[bottom].set(True)
        fresh = sample_hypers(jax.random.fold_in(kh, 0),
                              self.cfg["hyper_space"], n)
        bounds = hyper_bounds(self.cfg)
        out = {}
        for i, name in enumerate(sorted(hypers)):
            lo, hi = bounds[name]
            k1, k2 = jax.random.split(jax.random.fold_in(kh, 17 + i))
            up = jax.random.bernoulli(k1, 0.5, (n,))
            perturbed = jnp.clip(hypers[name] * jnp.where(
                up, p["perturb_scale"], 1.0 / p["perturb_scale"]), lo, hi)
            resample = jax.random.bernoulli(k2, p["perturb_prob"], (n,))
            out[name] = jnp.where(mask, jnp.where(resample, fresh[name],
                                                  perturbed), hypers[name])
        return state, out, parents

    def epoch(self):
        """One train-evolve epoch; returns the mean critic loss of each
        updating iteration, shape (iterations, N)."""
        tr = self.traffic
        per_iter = tr["collect_steps"] * tr["num_envs"]
        losses, evals = [], []
        key = self.key
        with jax.default_matmul_precision("highest"):
            for i in range(tr["pbt_interval"]):
                key, k_it = jax.random.split(key)
                kc, ks = jax.random.split(k_it)
                self.env_state, self.obs, self.buf = self._collect(
                    self.state["actor"], self.env_state, self.obs, self.buf,
                    jnp.int32(self.total % self.capacity), kc,
                    self.hypers["explore_noise"])
                self.total += per_iter
                if self.total >= self.cfg["batch_size"]:
                    self.state, loss = self._updates(
                        self.state, self.buf, jnp.int32(self.total), ks,
                        self.hypers)
                    losses.append(loss)
                if (i + 1) % tr["eval_every"] == 0:
                    key, k_ev = jax.random.split(key)
                    evals.append(self._evaluate(self.state["actor"], k_ev))
            fitness = jnp.mean(jnp.stack(evals), axis=0)
            key, k_evolve = jax.random.split(key)
            self.state, self.hypers, self.parents = self._evolve(
                k_evolve, self.state, self.hypers, fitness)
        self.key = key
        return jnp.stack(losses) if losses else jnp.zeros((0, self.n))


def run(cfg, traffic, seed, *, dtype="float32", steps=None):
    """The record the comparison reads, after ``steps`` epochs (default
    ``check_steps``): each epoch's critic losses, the first epoch's RMS
    gradient per leaf from Adam's second moment, and each leaf's change."""
    ref = Reference(cfg, traffic, seed, dtype=dtype)
    steps = steps or traffic["check_steps"]
    losses, grad, lineage = [], None, []
    for s in range(steps):
        losses.append(np.asarray(ref.epoch()))
        lineage.append(np.asarray(ref.parents))
        if s == 0:
            grad = rms_grad_norms(
                {name: (ref.state[f"{name}_opt"]["nu"],
                        ref.state[f"{name}_opt"]["t"])
                 for name in ("actor", "critic")}, cfg["adam"]["b2"])
    params = {"actor": ref.state["actor"], "critic": ref.state["critic"]}
    return {"losses": losses, "grad": grad, "lineage": lineage,
            "change": change_norms(params, ref.init_params)}
