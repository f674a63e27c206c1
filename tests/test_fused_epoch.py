"""The fused train–evolve epoch test wall.

``PopTrainer.run_env_loop(fused=True)`` executes whole epochs —
``pbt_interval`` fused iterations + evaluations + the strategy's evolve —
as ONE jitted donated program (``RolloutEngine.build_epoch``).  These tests
pin the three acceptance properties of that fusion:

  * BIT-EXACT against the eager loop — population state, hypers, key
    chain, step count, strategy internals and last fitness, across the
    algorithm registry and the PBT/CEM/DvD strategies (the eager and fused
    paths share one jitted evolve executable, so even CEM's distribution
    refit agrees bitwise);
  * ZERO steady-state recompiles — warm epochs re-enter cached
    executables (``repro.compat.register_compile_listener`` counts);
  * ZERO host round-trips — the warm loop runs under
    ``jax.transfer_guard("disallow")`` (device-to-host stays guarded;
    bookkeeping slices are scope-allowed int uploads only).

Plus the population-level update parity that makes the epoch possible:
``make_population_update`` (the hoisted ``population_adam`` path, with and
without ``fused_linear``) against ``vmap`` of the stock per-member update.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.base import PopulationConfig
from repro.envs import make
from repro.pop import PopTrainer, SharedCriticAgent
from repro.rl import get_algo, make_agent

ALGO_ENV = {"td3": "pendulum", "sac": "pendulum",
            "dqn": "cartpole", "ppo": "cartpole"}


def _build(algo, strategy, *, fused_adam=True, fused_linear=False,
           backend="vectorized", size=3, pbt_interval=4, fitness_window=10,
           seed=7):
    env = make(ALGO_ENV[algo])
    pcfg = PopulationConfig(
        size=size, strategy=strategy, backend=backend,
        num_steps=1 if algo == "ppo" else 2, pbt_interval=pbt_interval,
        fitness_window=fitness_window, donate=False,
        hyper_space=get_algo(algo).hyper_space,
        fused_adam=fused_adam, fused_linear=fused_linear)
    tr = PopTrainer(make_agent(algo, env.spec, hidden=(8, 8)), pcfg,
                    seed=seed)
    kwargs = dict(num_envs=2, collect_steps=8, eval_envs=2, eval_steps=20)
    if algo == "ppo":
        tr.attach_rollout(env, batch_size=16, epochs=1, **kwargs)
    else:
        tr.attach_rollout(env, batch_size=16, buffer_capacity=512, **kwargs)
    return tr


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def _assert_trees_close(a, b, msg="", **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   err_msg=msg, **tol)


def _assert_trainers_equal(ea, fu):
    _assert_trees_equal(ea.state, fu.state, "population state")
    np.testing.assert_array_equal(np.asarray(ea.key), np.asarray(fu.key),
                                  err_msg="trainer key chain")
    assert ea.step_count == fu.step_count
    assert (ea.hypers is None) == (fu.hypers is None)
    if ea.hypers is not None:
        _assert_trees_equal(ea.hypers, fu.hypers, "hypers")
    _assert_trees_equal(ea.strategy.export_state(),
                        fu.strategy.export_state(), "strategy state")
    assert (ea.last_fitness is None) == (fu.last_fitness is None)
    if ea.last_fitness is not None:
        np.testing.assert_array_equal(np.asarray(ea.last_fitness),
                                      np.asarray(fu.last_fitness),
                                      err_msg="last_fitness")
    assert len(ea._window) == len(fu._window)
    for wa, wb in zip(ea._window, fu._window):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb),
                                      err_msg="fitness window")


# ----------------------------------------- population-update parity
@pytest.mark.parametrize("algo", sorted(ALGO_ENV))
def test_population_update_matches_vmap_of_stock(algo):
    """fused_adam=True swaps vmap(stock update) for the module's
    population-level update (optimizer hoisted into population_adam):
    same training trajectory to float tolerance, per-member hypers
    included."""
    a = _build(algo, "pbt", fused_adam=False, pbt_interval=100)
    b = _build(algo, "pbt", fused_adam=True, pbt_interval=100)
    a.run_env_loop(4, eval_every=2)
    b.run_env_loop(4, eval_every=2)
    _assert_trees_close(a.state, b.state, f"{algo} pop-update parity",
                        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algo", sorted(ALGO_ENV))
def test_fused_linear_matches_member_linears(algo):
    """fused_linear routes the member forwards through the population-
    batched pop_* applies; off-TPU that is the batched-einsum fallback,
    which lowers to the same dot_general as the vmap — bitwise."""
    a = _build(algo, "pbt", fused_adam=True, pbt_interval=100)
    b = _build(algo, "pbt", fused_adam=True, fused_linear=True,
               pbt_interval=100)
    a.run_env_loop(4, eval_every=2)
    b.run_env_loop(4, eval_every=2)
    _assert_trees_close(a.state, b.state, f"{algo} fused_linear parity",
                        rtol=1e-5, atol=1e-6)


def test_shared_critic_fused_linear_parity():
    """The §4.2 shared-critic update under fused_linear: member policy
    forwards go population-batched, the (axis-free) shared critic stays on
    the plain apply — same update to float tolerance."""
    from repro.core import shared
    key = jax.random.PRNGKey(0)
    n, B, obs, act = 4, 8, 3, 1
    st = shared.init(key, obs, act, n)
    batch = {"obs": jax.random.normal(key, (n, B, obs)),
             "action": jax.random.normal(key, (n, B, act)),
             "reward": jax.random.normal(key, (n, B)),
             "next_obs": jax.random.normal(key, (n, B, obs)),
             "done": jnp.zeros((n, B))}
    s0, m0 = jax.jit(shared.make_shared_critic_update(fused_adam=True))(
        st, batch, None)
    s1, m1 = jax.jit(shared.make_shared_critic_update(
        fused_adam=True, fused_linear=True))(st, batch, None)
    _assert_trees_close(s0, s1, "shared-critic fused_linear",
                        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m0["critic_loss"]),
                               float(m1["critic_loss"]), rtol=1e-5)


# ------------------------------------------------ epoch bit-exactness
@pytest.mark.parametrize("algo,strategy",
                         [(a, s) for a in sorted(ALGO_ENV)
                          for s in ("pbt", "cem", "dvd")])
def test_fused_epoch_bitwise_vs_eager(algo, strategy):
    """Two epochs (8 iters, evolve every 4, eval every 2) through the
    fused path reproduce the eager loop BITWISE — state, hypers, key
    chain, strategy internals, last fitness, window — over the full
    algorithm registry x strategy grid (CEM's distribution refit agrees
    bitwise because eager and fused share ONE jitted evolve
    executable)."""
    ea = _build(algo, strategy)
    fu = _build(algo, strategy)
    ea.run_env_loop(8, eval_every=2)
    fu.run_env_loop(8, eval_every=2, fused=True)
    _assert_trainers_equal(ea, fu)


def test_fused_epoch_bitwise_non_evolving():
    """Below the evolve cadence the epoch is just fused iterations +
    evaluations; the fitness window must fill with the same device rows."""
    ea = _build("td3", "none")
    fu = _build("td3", "none")
    ea.run_env_loop(4, eval_every=2)
    fu.run_env_loop(4, eval_every=2, fused=True)
    _assert_trainers_equal(ea, fu)
    assert len(fu._window) == 2


def test_fused_epoch_resumes_across_calls():
    """Back-to-back fused calls chain exactly like one longer eager run
    (the epoch cache re-enters the compiled executable)."""
    ea = _build("td3", "pbt")
    fu = _build("td3", "pbt")
    ea.run_env_loop(16, eval_every=2)
    fu.run_env_loop(8, eval_every=2, fused=True)
    fu.run_env_loop(8, eval_every=2, fused=True)
    _assert_trainers_equal(ea, fu)


# ------------------------------------- recompiles and host transfers
def test_fused_epoch_zero_steady_state_recompiles():
    tr = _build("td3", "pbt")
    tr.run_env_loop(8, eval_every=2, fused=True)   # warm: traces epoch+evolve
    events = []
    cancel = compat.register_compile_listener(
        lambda info: events.append(info))
    try:
        tr.run_env_loop(8, eval_every=2, fused=True)
    finally:
        cancel()
    assert not events, f"steady-state recompiles: {events}"


def test_fused_epoch_no_host_round_trips():
    """The acceptance property: a warm fused epoch — including the evolve
    and all host-side bookkeeping — runs under transfer_guard('disallow').
    The trainer scope-allows its python-int bookkeeping uploads; anything
    fetching device values back to the host would still raise."""
    tr = _build("td3", "pbt")
    tr.run_env_loop(8, eval_every=2, fused=True)
    with jax.transfer_guard("disallow"):
        metrics, stats = tr.run_env_loop(8, eval_every=2, fused=True)
    assert isinstance(metrics["critic_loss"], jax.Array)
    assert np.isfinite(np.asarray(metrics["critic_loss"])).all()


# ------------------------------------------------- alignment guards
def test_fused_epoch_alignment_errors():
    tr = _build("td3", "pbt")
    with pytest.raises(ValueError, match="multiple of pbt_interval"):
        tr.run_env_loop(6, eval_every=2, fused=True)
    with pytest.raises(ValueError, match="divide pbt_interval"):
        tr.run_env_loop(8, eval_every=3, fused=True)
    tr2 = _build("td3", "pbt", fitness_window=1)
    with pytest.raises(ValueError, match="overflow fitness_window"):
        tr2.run_env_loop(8, eval_every=2, fused=True)
    tr3 = _build("td3", "pbt")
    tr3.report_fitness(jnp.zeros(3))
    with pytest.raises(ValueError, match="non-empty"):
        tr3.run_env_loop(8, eval_every=2, fused=True)


def test_fused_epoch_misaligned_step_count_errors():
    tr = _build("td3", "pbt")
    tr.run_env_loop(1, eval_every=0)          # eager, no window -> no evolve
    with pytest.raises(ValueError, match="not epoch-aligned"):
        tr.run_env_loop(8, eval_every=2, fused=True)


def test_fused_epoch_boundary_crossing_errors():
    tr = _build("td3", "pbt")
    tr.run_env_loop(3, eval_every=0)          # step_count = 3
    with pytest.raises(ValueError, match="crosses an evolve boundary"):
        tr.run_env_loop(2, eval_every=2, fused=True)


# ------------------------------------------------------ islands (8 dev)
needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="islands fused-epoch tests want 8 (fake) devices; run with "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@needs_devices
def test_fused_epoch_bitwise_on_islands():
    """The fused epoch shard_maps over the 'pop' mesh axis unchanged: the
    islands backend reproduces its own eager loop's TRAINING path bitwise —
    population state, key chain, step count.

    Evaluation fitness is compared structurally, not bitwise: on a multi-
    device runtime XLA re-fuses the evaluator inlined into the epoch
    program at ~1 ULP vs the eager standalone executable (measured 4e-9 on
    the policy forward, replicated params included), and twenty steps of
    chaotic pendulum dynamics amplify a ULP to O(1) episode returns.  The
    shard_mapped update path has a pinned program boundary, so the state
    trajectory stays bitwise — which is what the fusion must preserve."""
    ea = _build("td3", "none", backend="islands", size=4)
    fu = _build("td3", "none", backend="islands", size=4)
    ea.run_env_loop(4, eval_every=2)
    fu.run_env_loop(4, eval_every=2, fused=True)
    _assert_trees_equal(ea.state, fu.state, "islands population state")
    np.testing.assert_array_equal(np.asarray(ea.key), np.asarray(fu.key),
                                  err_msg="islands key chain")
    assert ea.step_count == fu.step_count
    assert len(ea._window) == len(fu._window) == 2
    for wa, wb in zip(ea._window, fu._window):
        assert np.asarray(wb).shape == np.asarray(wa).shape
        assert np.isfinite(np.asarray(wb)).all()


@needs_devices
def test_fused_epoch_evolves_on_islands():
    """The full train–evolve epoch runs sharded: evolve fires on device,
    the population state stays partitioned over the 'pop' mesh axis, and
    warm epochs re-enter the cached executable (zero recompiles)."""
    tr = _build("td3", "pbt", backend="islands", size=4)
    tr.run_env_loop(8, eval_every=2, fused=True)
    assert tr.last_fitness is not None
    assert np.isfinite(np.asarray(tr.last_fitness)).all()
    events = []
    cancel = compat.register_compile_listener(
        lambda info: events.append(info))
    try:
        tr.run_env_loop(8, eval_every=2, fused=True)
    finally:
        cancel()
    assert not events, f"islands steady-state recompiles: {events}"
    for leaf in jax.tree.leaves(tr.state):
        assert np.isfinite(np.asarray(leaf)).all()
        assert "pop" in str(leaf.sharding), (
            f"fused epoch lost the 'pop' sharding: {leaf.sharding}")


@needs_devices
def test_islands_fused_update_matches_vectorized():
    """Sharding decides WHERE members update, never what they compute: the
    population-level fused_adam + fused_linear update under shard_map
    tracks the single-device vectorized backend on identical batches (the
    fused companion of test_elastic's islands-numerics check)."""
    from repro.pop import ModuleAgent
    from repro.rl import td3
    from repro.configs.base import HyperSpace
    n, bsz, obs, act = 8, 16, 3, 1
    space = HyperSpace(log_uniform=(("actor_lr", 3e-5, 3e-3),))
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    batch = {"obs": jax.random.normal(ks[0], (n, bsz, obs)),
             "action": jax.random.uniform(ks[1], (n, bsz, act),
                                          minval=-1, maxval=1),
             "reward": jax.random.normal(ks[2], (n, bsz)),
             "next_obs": jax.random.normal(ks[3], (n, bsz, obs)),
             "done": jnp.zeros((n, bsz))}
    out = {}
    for backend in ("vectorized", "islands"):
        pcfg = PopulationConfig(size=n, strategy="pbt", backend=backend,
                                hyper_space=space, donate=False,
                                pbt_interval=0, fused_adam=True,
                                fused_linear=True)
        tr = PopTrainer(ModuleAgent(td3, obs, act), pcfg, seed=0)
        for _ in range(2):
            tr.step(batch)
        out[backend] = jax.device_get(tr.state)
    _assert_trees_close(out["vectorized"], out["islands"],
                        "islands vs vectorized fused update",
                        rtol=1e-5, atol=1e-5)


def test_fused_epoch_compiled_text_carries_its_scopes():
    """The compiled epoch names its parts: collect, update, eval and
    evolve appear as ``jax.named_scope`` in the instructions' op_name."""
    import re
    tr = _build("td3", "pbt")
    r = tr.rollout
    fn = tr._fused_epoch(4, 2, True)
    text = fn.lower(tr.state, r.bufs, r.vstate, tr.hypers,
                    tr.strategy.export_state(), tr.key).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("collect", "update", "eval", "evolve"):
        assert any(re.search(rf"(^|/|\(){scope}(\)|/|$)", n)
                   for n in op_names), scope
