"""The overlapped acting engine test wall (``repro.rollout.overlap``).

Pins the acceptance properties of the split collect/update pipeline:

  * ``policy_lag=0`` is the PARITY ANCHOR — bitwise-identical trainer
    state, key chain, buffers and env state against the serial fused
    engine, across all four algorithms (the two-program split with the
    serial key discipline must be a pure refactor at lag 0);
  * ``policy_lag=1`` has the declared OFF-BY-ONE property — collect for
    iteration t+1 acts with the params captured BEFORE update t, and
    update t consumes exactly the slot collect t-1 produced;
  * CHUNKED collection (``chunk_steps``) is bitwise-equal to unchunked —
    scanning fixed-size chunks through the ring must insert the same
    transitions with the same key chain;
  * ZERO steady-state recompiles at lag 1 (both programs re-enter their
    caches) and no implicit host transfers post-warmup;
  * ``restore_elastic`` installs the background-AOT executables (the
    resize-time recompile overlaps data movement); an AOT executable hands
    over to the jit path only when it refuses its arguments, and keeps
    running on a sharded islands mesh;
  * telemetry: ``block_every`` emits ``blocks`` dispatch/wait split rows
    that ``tools/report.py`` summarizes and ``--check`` accepts.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.base import PopulationConfig
from repro.envs import make
from repro.pop import PopTrainer
from repro.rl import get_algo, make_agent
from repro.rollout import OverlapEngine, RolloutEngine

ALGO_ENV = {"td3": "pendulum", "sac": "pendulum",
            "dqn": "cartpole", "ppo": "cartpole"}


def _build(algo, *, policy_lag=None, chunk_steps=None, size=3, seed=7,
           strategy="pbt", pbt_interval=100, checkpoint_dir=None):
    env = make(ALGO_ENV[algo])
    pcfg = PopulationConfig(
        size=size, strategy=strategy, backend="vectorized",
        num_steps=1 if algo == "ppo" else 2, pbt_interval=pbt_interval,
        fitness_window=10, donate=False,
        hyper_space=get_algo(algo).hyper_space)
    tr = PopTrainer(make_agent(algo, env.spec, hidden=(8, 8)), pcfg,
                    seed=seed, checkpoint_dir=checkpoint_dir)
    kwargs = dict(num_envs=2, collect_steps=8, eval_envs=2, eval_steps=20,
                  policy_lag=policy_lag, chunk_steps=chunk_steps)
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


def _assert_engines_equal(ta, tb, msg=""):
    _assert_trees_equal(ta.state, tb.state, f"{msg}: population state")
    np.testing.assert_array_equal(np.asarray(ta.key), np.asarray(tb.key),
                                  err_msg=f"{msg}: trainer key chain")
    _assert_trees_equal(ta.rollout.bufs, tb.rollout.bufs,
                        f"{msg}: experience buffers")
    _assert_trees_equal(ta.rollout.vstate, tb.rollout.vstate,
                        f"{msg}: env state")


def _run(tr, iters=5, eval_every=2):
    tr.run_env_loop(iters, eval_every=eval_every)
    return tr


# --------------------------------------------------- lag=0 parity anchor
@pytest.mark.parametrize("algo", sorted(ALGO_ENV))
def test_lag0_bitwise_matches_serial(algo):
    """The two-program split at policy_lag=0 is a pure refactor of the
    serial fused iteration: identical state, keys, buffers, env state."""
    serial = _run(_build(algo))
    assert isinstance(serial.rollout, RolloutEngine)
    assert not isinstance(serial.rollout, OverlapEngine)
    lag0 = _run(_build(algo, policy_lag=0))
    assert isinstance(lag0.rollout, OverlapEngine)
    _assert_engines_equal(serial, lag0, f"{algo} lag0 vs serial")


# ----------------------------------------------------- chunked collection
@pytest.mark.parametrize("algo", ["td3", "ppo"])
def test_chunked_collect_bitwise_matches_unchunked(algo):
    """Scanning collect in fixed-size chunks (bounded memory at thousands
    of envs) must not change a single bit: same key chain, same ring
    positions, same training trajectory."""
    whole = _run(_build(algo))
    chunked = _run(_build(algo, chunk_steps=4))
    _assert_engines_equal(whole, chunked, f"{algo} chunked vs whole")


def test_chunk_steps_must_divide_collect_steps():
    with pytest.raises(ValueError, match="chunk_steps"):
        _build("td3", chunk_steps=3)   # collect_steps=8


# --------------------------------------------------- lag=1 staleness law
@pytest.mark.parametrize("algo", ["td3", "ppo"])
def test_lag1_off_by_one_property(algo):
    """The declared semantics of the overlapped path: collect for t+1 uses
    actors(state_t) captured BEFORE update t ran, and update t consumes
    exactly the slot the previous collect produced."""
    tr = _build(algo, policy_lag=1)
    eng = tr.rollout
    calls = []
    orig = eng._call

    def spy(which, *args):
        out = orig(which, *args)
        calls.append((which, args, out))
        return out

    eng._call = spy
    pre_states = []
    for _ in range(4):
        pre_states.append(tr.state)
        tr.env_iteration()

    # call sequence: prologue collect, then (update, collect) per iteration
    kinds = [c[0] for c in calls]
    assert kinds == ["collect"] + ["update", "collect"] * 4

    collects = [c for c in calls if c[0] == "collect"]
    updates = [c for c in calls if c[0] == "update"]
    for t, up in enumerate(updates):
        # update(t) trains on the slot produced by collect(t-1) — the
        # prologue's slot for t=0 (identity, not value, equality)
        slot_consumed = up[1][2]
        slot_produced = collects[t][2][1]
        assert jax.tree.leaves(slot_consumed)[0] is \
            jax.tree.leaves(slot_produced)[0], f"update {t} wrong slot"
        # update(t) sees state_t...
        _assert_trees_equal(up[1][0], pre_states[t],
                            f"update {t} state")
    for t, co in enumerate(collects[1:]):
        # ...while collect(t+1), dispatched in the SAME iterate() call,
        # acts with the actors of state_t — pre-update params: one behind
        _assert_trees_equal(
            co[1][0], eng.agent.actor_params(pre_states[t]),
            f"collect {t + 1} actor params not one update behind")


def test_lag1_runs_and_trains(tmp_path):
    """End-to-end sanity at lag=1: finite metrics, buffers fill, evolve
    cadence works, export/import drops the in-flight slot cleanly."""
    tr = _build("td3", policy_lag=1, pbt_interval=3)
    tr.run_env_loop(6, eval_every=1)
    assert tr.rollout._pending is not None
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(tr.state))
    state = tr.rollout.export_state()
    tr.rollout.import_state(state)
    assert tr.rollout._pending is None     # restore re-runs the prologue
    tr.run_env_loop(2, eval_every=1)


def test_lag1_validates_lag_values():
    with pytest.raises(ValueError, match="policy_lag"):
        _build("td3", policy_lag=2)


def test_lag1_fused_epoch_unsupported():
    tr = _build("td3", policy_lag=1)
    with pytest.raises(NotImplementedError):
        tr.rollout.build_epoch(epoch_len=4)
    with pytest.raises(NotImplementedError):
        tr.run_env_loop(4, eval_every=0, fused=True)


# ------------------------------------------- steady-state recompiles = 0
def test_lag1_zero_steady_state_recompiles():
    tr = _build("td3", policy_lag=1)
    for _ in range(2):       # warm both programs (prologue + full pipe)
        tr.env_iteration()
    events = []
    unregister = compat.register_compile_listener(
        lambda e, s: events.append(e))
    if unregister is None:
        pytest.skip("no jax.monitoring surface")
    try:
        for _ in range(3):
            tr.env_iteration()
        jax.block_until_ready((tr.state, tr.rollout._pending))
    finally:
        unregister()
    assert events == [], f"steady-state recompiles: {events}"


def test_lag1_no_host_transfers_post_warmup():
    tr = _build("td3", policy_lag=1)
    for _ in range(2):
        tr.env_iteration()
    with jax.transfer_guard("disallow"):
        tr.env_iteration()


# ------------------------------------------------ elastic AOT installing
@pytest.mark.parametrize("policy_lag", [None, 1])
def test_restore_elastic_installs_aot_executables(tmp_path, policy_lag):
    """restore_elastic starts the new topology's compile on a background
    thread while resize_tree moves data; by return the engine must be
    running the AOT executables, and iteration must work."""
    from repro.elastic import restore_elastic

    src = _build("td3", size=3, checkpoint_dir=str(tmp_path))
    src.run_env_loop(3, eval_every=1)
    src.save(blocking=True)

    dst = _build("td3", size=2, policy_lag=policy_lag,
                 checkpoint_dir=str(tmp_path))
    step, lineage = restore_elastic(dst)
    eng = dst.rollout
    if policy_lag is None:
        assert eng._iteration_exec is not eng._iteration, \
            "serial engine still on lazy jit after restore_elastic"
    else:
        assert eng._exec["update"] is not eng._progs["update"], \
            "overlap engine still on lazy jit after restore_elastic"
        assert eng._exec["collect"] is not eng._progs["collect"]
    dst.run_env_loop(2, eval_every=1)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(dst.state))


@pytest.mark.parametrize("policy_lag", [None, 1])
def test_aot_fallback_only_on_argument_mismatch(policy_lag):
    """An AOT executable that refuses its arguments hands over to the jit
    path for good; any other error (an OOM, a runtime fault) propagates
    instead of being retried on the jit path."""
    tr = _build("td3", policy_lag=policy_lag)
    tr.env_iteration()
    eng = tr.rollout
    refuses = jax.jit(lambda x: x).lower(
        jax.ShapeDtypeStruct((1,), jnp.float32)).compile()

    def faults(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    def install(fn):
        if policy_lag is None:
            eng._iteration_exec = fn
        else:
            eng._exec = {"collect": fn, "update": fn}

    install(faults)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        tr.env_iteration()
    install(refuses)
    tr.env_iteration()
    if policy_lag is None:
        assert eng._iteration_exec is eng._iteration
    else:
        assert eng._exec == eng._progs


AOT_ISLANDS = """
import json, sys
import jax
from repro.configs.base import PopulationConfig
from repro.elastic import plan_layout
from repro.envs import make
from repro.pop import PopTrainer
from repro.rl import get_algo, make_agent

lag = None if sys.argv[1] == "none" else int(sys.argv[1])
env = make("pendulum")
pcfg = PopulationConfig(size=2, strategy="none", backend="islands",
                        num_steps=2, donate=False,
                        hyper_space=get_algo("td3").hyper_space)
tr = PopTrainer(make_agent("td3", env.spec, hidden=(8, 8)), pcfg, seed=0,
                layout=plan_layout(len(jax.devices()), 2))
tr.attach_rollout(env, num_envs=2, collect_steps=8, batch_size=16,
                  buffer_capacity=256, eval_envs=1, policy_lag=lag)
tr.env_iteration()
eng = tr.rollout
err = eng.warm_compile_async(tr.state, tr.hypers, tr.key)()
aot = eng._iteration_exec if lag is None else dict(eng._exec)
for _ in range(2):
    tr.env_iteration()
kept = eng._iteration_exec is aot if lag is None else eng._exec == aot
print(json.dumps({"error": None if err is None else repr(err),
                  "kept": kept}))
"""


@pytest.mark.parametrize("policy_lag", ["none", "1"])
def test_aot_executables_keep_running_on_islands_mesh(policy_lag):
    """The AOT lowering takes each argument's sharding, so on a 2-device
    islands mesh the executables accept the sharded state and are kept —
    not refused on every call and silently replaced by a recompile."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", AOT_ISLANDS, policy_lag],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"error": None, "kept": True}


# ----------------------------------------------- dispatch/block telemetry
def test_block_telemetry_rows_and_report(tmp_path, capsys):
    """run_env_loop(block_every=1) times an explicit block_until_ready per
    iteration into the iter rows' ``blocks`` field; tools/report.py
    summarizes it and --check accepts the file."""
    from repro.telemetry import JSONLSink, RunTelemetry

    path = tmp_path / "run.jsonl"
    tr = _build("td3", policy_lag=1)
    tr.telemetry = RunTelemetry(JSONLSink(path, strict=True))
    tr.run_env_loop(3, eval_every=1, block_every=1)
    tr.telemetry.close()

    rows = [json.loads(line) for line in path.read_text().splitlines()]
    iters = [r for r in rows if r["kind"] == "iter"]
    assert len(iters) == 3
    assert all("blocks" in r and "iterate" in r["blocks"] for r in iters)
    assert all("phases" in r for r in iters)

    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "tools"))
    try:
        import report
    finally:
        sys.path.pop(0)
    blocks = report.block_summary(iters)
    assert "iterate" in blocks
    assert report.check_rows(rows) == []
    report.report(rows)
    out = capsys.readouterr().out
    assert "blocks" in out


def test_block_every_rejects_fused():
    tr = _build("td3")
    with pytest.raises(ValueError, match="block_every"):
        tr.run_env_loop(4, fused=True, block_every=1)
