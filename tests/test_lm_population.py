"""LM population training: backend parity, fused population-Adam bitwise
equivalence, grad accumulation, model-sharded islands, elastic checkpoint
resize, and PBT lineage replay through ``tools/report.py``.

The acceptance surface of the LM-in-the-hot-path work: LMAgent runs through
the SAME backend registry as the RL agents, and the hoisted
``repro.optim.population_adam`` step is bitwise-equal to stock
optax-under-vmap on the fp32 ``rwkv6_test`` config.

The islands test needs 8 (fake) devices — CI's tier-2 ``lm`` job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; under the tier-1
single-device run it skips.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.configs.base import HyperSpace, PopulationConfig
from repro.pop import LMAgent, PopTrainer, make_update
from repro.telemetry import JSONLSink, RunTelemetry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import report  # noqa: E402

CFG = get_config("rwkv6_test")
TCFG = TrainConfig(total_steps=50, warmup_steps=5, lr=1e-3,
                   weight_decay=0.1)
N = 3


def _pop_state(agent, n=N, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return jax.vmap(agent.init)(keys)


def _batch(n=N, b=2, s=32, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (n, b, s),
                                0, CFG.vocab_size)
    return {"tokens": tokens}


def _hypers(n=N):
    return {"lr_scale": jnp.linspace(0.5, 2.0, n),
            "weight_decay": jnp.linspace(0.01, 0.2, n),
            "warmup_frac": jnp.linspace(0.05, 0.2, n)}


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state.params)]


# ------------------------------------------------------- backend parity
@pytest.mark.parametrize("hypers", [None, "pbt"], ids=["plain", "hypers"])
def test_vectorized_matches_sequential(hypers):
    agent = LMAgent(CFG, TCFG)
    h = _hypers() if hypers else None
    state0, batch = _pop_state(agent), _batch()
    vec = make_update(agent, "vectorized", donate=False)
    seq = make_update(agent, "sequential", donate=False)
    sv, mv = vec(state0, batch, h)
    ss, ms = seq(state0, batch, h)
    np.testing.assert_allclose(np.asarray(mv["loss"]),
                               np.asarray(ms["loss"]), rtol=2e-5)
    for a, b in zip(_leaves(sv), _leaves(ss)):
        np.testing.assert_allclose(a, b, atol=2e-5)


# ------------------------------------------- fused population-Adam parity
@pytest.mark.parametrize("hypers", [None, "pbt"], ids=["plain", "hypers"])
def test_fused_adam_bitwise_equals_stock(hypers):
    h = _hypers() if hypers else None
    stock = LMAgent(CFG, TCFG)
    fused = LMAgent(CFG, TCFG, fused_adam=True)
    state0, batch = _pop_state(stock), _batch()
    up_stock = make_update(stock, "vectorized", donate=False)
    up_fused = make_update(fused, "vectorized", donate=False)
    # two chained steps so second-step state (m, v, step counter) matters
    s1, m1 = up_stock(state0, batch, h)
    s2, m2 = up_fused(state0, batch, h)
    assert np.array_equal(np.asarray(m1["loss"]), np.asarray(m2["loss"]))
    b2 = _batch(seed=2)
    s1, m1 = up_stock(s1, b2, h)
    s2, m2 = up_fused(s2, b2, h)
    assert np.array_equal(np.asarray(m1["loss"]), np.asarray(m2["loss"]))
    for a, b in zip(_leaves(s1), _leaves(s2)):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b), "fused pop-Adam diverged bitwise"


# ------------------------------------------------------- grad accumulation
def test_grad_accum_matches_single_pass():
    from repro.models import lm as L
    b, s, accum = 4, 32, 4
    params = L.init_params(jax.random.PRNGKey(0), CFG)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s),
                                          0, CFG.vocab_size)}
    outs = {}
    for accum in (1, 4):
        tcfg = TCFG.replace(grad_accum=accum) \
            if hasattr(TCFG, "replace") else \
            TrainConfig(total_steps=50, warmup_steps=5, lr=1e-3,
                        weight_decay=0.1, grad_accum=accum)
        opt_init, train_step = L.make_train_step(CFG, tcfg)
        p2, _, metrics = jax.jit(train_step)(
            params, opt_init(params), batch, jnp.zeros((), jnp.int32))
        outs[accum] = (p2, float(metrics["loss"]))
    assert abs(outs[1][1] - outs[4][1]) < 1e-4
    for a, b in zip(jax.tree.leaves(outs[1][0]),
                    jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# --------------------------------------------- model-sharded islands (8 dev)
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="islands layout test needs 8 (fake) devices")
def test_islands_model_sharded_matches_vectorized():
    from repro.elastic import plan_layout
    n = 4
    layout = plan_layout(8, n, preferred_model=2)
    assert layout.model == 2 and layout.islands * layout.data == 4
    agent = LMAgent(CFG, TCFG)
    assert agent.model_sharded_params
    state0, batch, h = _pop_state(agent, n), _batch(n), _hypers(n)

    vec = make_update(agent, "vectorized", donate=False)
    sv, mv = vec(state0, batch, h)

    placed = layout.place(state0, model_rules=True)
    isl = make_update(agent, "islands", donate=False, mesh=layout.mesh)
    si, mi = isl(placed, batch, h)

    np.testing.assert_allclose(np.asarray(mv["loss"]),
                               np.asarray(mi["loss"]), rtol=2e-5)
    for a, b in zip(_leaves(sv), _leaves(si)):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="islands layout test needs 8 (fake) devices")
def test_islands_trainer_end_to_end():
    pcfg = PopulationConfig(
        size=4, strategy="pbt", backend="islands", donate=False,
        pbt_interval=2, fitness_window=2,
        hyper_space=HyperSpace(
            log_uniform=(("lr_scale", 0.1, 10.0),
                         ("weight_decay", 1e-3, 0.3)),
            uniform=(("warmup_frac", 0.01, 0.25),)))
    from repro.elastic import plan_layout
    tr = PopTrainer(LMAgent(CFG, TCFG), pcfg, seed=0,
                    layout=plan_layout(8, 4, preferred_model=2))
    losses = []
    for i in range(4):
        metrics, _ = tr.step(_batch(4, seed=i))
        losses.append(np.asarray(metrics["loss"]))
    assert all(np.all(np.isfinite(l)) for l in losses)
    assert set(tr.hypers) == {"lr_scale", "weight_decay", "warmup_frac"}


# --------------------------------------------- elastic checkpoint resize
def test_checkpoint_restore_elastic_resize(tmp_path):
    from repro.elastic.relayout import restore_elastic
    space = HyperSpace(log_uniform=(("lr_scale", 0.1, 10.0),),
                       uniform=(("warmup_frac", 0.01, 0.25),))
    pcfg = PopulationConfig(size=4, strategy="pbt", donate=False,
                            pbt_interval=2, fitness_window=2,
                            hyper_space=space)
    tr = PopTrainer(LMAgent(CFG, TCFG), pcfg, seed=0,
                    checkpoint_dir=str(tmp_path))
    for i in range(3):
        tr.step(_batch(4, seed=i))
    tr.save(blocking=True)

    pcfg2 = PopulationConfig(size=2, strategy="pbt", donate=False,
                             pbt_interval=2, fitness_window=2,
                             hyper_space=space)
    tr2 = PopTrainer(LMAgent(CFG, TCFG), pcfg2, seed=1,
                     checkpoint_dir=str(tmp_path))
    step, lineage = restore_elastic(tr2)
    assert step == 2 and len(lineage) == 2  # save() records step_count - 1
    # restored members carry the checkpointed params of their parents
    src = {i: np.asarray(jax.tree.leaves(tr.state.params)[0][int(p)])
           for i, p in enumerate(lineage)}
    dst = np.asarray(jax.tree.leaves(tr2.state.params)[0])
    for i, p in src.items():
        assert np.array_equal(dst[i], p)
    metrics, _ = tr2.step(_batch(2, seed=9))
    assert np.all(np.isfinite(np.asarray(metrics["loss"])))


# ------------------------------------------------ PBT lineage via report.py
def test_lm_pbt_lineage_replays_through_report(tmp_path):
    log = tmp_path / "telemetry.jsonl"
    pcfg = PopulationConfig(
        size=4, strategy="pbt", donate=False, pbt_interval=2,
        fitness_window=2,
        hyper_space=HyperSpace(
            log_uniform=(("lr_scale", 0.1, 10.0),
                         ("weight_decay", 1e-3, 0.3)),
            uniform=(("warmup_frac", 0.01, 0.25),)))
    tel = RunTelemetry(JSONLSink(log, strict=True),
                       meta={"arch": "rwkv6_test"})
    tr = PopTrainer(LMAgent(CFG, TCFG), pcfg, seed=0, telemetry=tel)
    for i in range(6):
        tr.step(_batch(4, seed=i))
    tel.close()

    rows = report.load_rows(log)
    assert report.check_rows(rows) == []
    evolves = [r for r in rows if r["kind"] == "evolve"]
    assert [e["step"] for e in evolves] == [2, 4, 6]
    roots, children, current = report.lineage_tree(rows)
    assert len(roots) == 4 and set(current) == set(range(4))
    # hyper trajectories carry the LM tuning set end to end
    traj = report.hyper_trajectories(rows)
    assert {"lr_scale", "weight_decay", "warmup_frac"} <= set(traj)
    # each iter row carries the host CPU time of its phases beside their
    # wall time: the update call nested in the whole step, the evolve on
    # its cadence, and no more CPU time than wall time (clock granularity
    # aside)
    iters = [r for r in rows if r["kind"] == "iter"]
    assert len(iters) == 6
    for r in iters:
        assert {"step", "update"} <= set(r["phases_cpu"])
        assert set(r["phases_cpu"]) == set(r["phases"])
        assert all(0 <= cpu <= r["phases"][k] + 1e-3
                   for k, cpu in r["phases_cpu"].items())
        assert r["phases_cpu"]["update"] <= r["phases_cpu"]["step"] + 1e-3
    assert sum("evolve" in r["phases_cpu"] for r in iters) == 3


# ------------------------------------------------- device scopes of the step
def test_lm_step_compiled_text_carries_the_four_scopes():
    """The qwen2 step at smoke shapes: every scope names instructions of
    the compiled program, the layer scans' ``while`` loops among them
    (forward and backward), so a profiler trace can be split by them."""
    import re
    cfg = get_config("qwen2_0_5b").smoke()
    agent = LMAgent(cfg, TCFG)
    state = agent.population_init(jax.random.PRNGKey(0), 1)
    batch = {"tokens": jnp.zeros((1, 2, 16), jnp.int32)}
    text = make_update(agent, "vectorized", donate=False).lower(
        state, batch, None).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("embed", "layers", "head", "optimizer"):
        assert any(f"({scope})" in n or f"/{scope}/" in n
                   for n in op_names), scope
    loops = re.findall(r'while\(.*op_name="([^"]*)"', text)
    assert any("jvp(layers))/while" in n for n in loops), loops
    assert any("transpose(jvp(layers))" in n for n in loops), loops
