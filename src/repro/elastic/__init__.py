"""``repro.elastic`` — device topology + elasticity for population training.

The paper's §5 protocols "extend to large population sizes when provided
with a few accelerators"; this package is that claim as a subsystem:

  * :mod:`repro.elastic.layout`   — :class:`IslandLayout` /
    :func:`plan_layout`: partition the available devices into per-group
    islands (population x data x model axes) from nothing but the device
    count and the population size; :func:`plan_mesh` is the (data, model)
    grid planner for a single large member.
  * :mod:`repro.elastic.islands`  — the ``"islands"`` update backend
    (``jax.shard_map`` over the ``"pop"`` mesh axis), registered
    in the ``repro.pop`` backend registry: a one-line config swap.
  * :mod:`repro.elastic.resize`   — elastic population shrink/grow (worst
    members dropped, PBT clones refill), applied uniformly to training
    state, hypers, replay buffers and env states.
  * :mod:`repro.elastic.relayout` — :func:`restore_elastic`: resume a
    ``PopTrainer`` + attached ``RolloutEngine`` from a checkpoint onto a
    different device count and/or population size.

Worked example — train 8 members across whatever devices exist, lose half
the machine, resume with 6 members on the survivors::

    from repro.configs.base import PopulationConfig
    from repro.elastic import plan_layout, restore_elastic
    from repro.envs import make
    from repro.pop import ModuleAgent, PopTrainer
    from repro.rl import td3

    env = make("pendulum")
    agent = ModuleAgent(td3, env.spec.obs_dim, env.spec.act_dim)
    pcfg = PopulationConfig(size=8, strategy="pbt", backend="islands",
                            donate=False)
    trainer = PopTrainer(agent, pcfg, checkpoint_dir="/tmp/ckpt")
    trainer.attach_rollout(env)
    trainer.run_env_loop(50)
    trainer.save(blocking=True)

    # --- restart on a 4-device machine with 6 members --------------------
    pcfg = PopulationConfig(size=6, strategy="pbt", backend="islands",
                            donate=False)
    trainer = PopTrainer(agent, pcfg, layout=plan_layout(4, 6),
                         checkpoint_dir="/tmp/ckpt")
    trainer.attach_rollout(env)
    step, lineage = restore_elastic(trainer)  # 2 least-fit members dropped;
    trainer.run_env_loop(50)                  # buffers + env states intact
"""
from repro.elastic.layout import (  # noqa: F401
    IslandLayout, plan_layout, plan_mesh,
)
from repro.elastic.resize import (  # noqa: F401
    grow_population, plan_resize, resize_tree, shrink_population,
)
from repro.elastic.relayout import relayout, restore_elastic  # noqa: F401
from repro.elastic import islands as _islands  # noqa: F401  (registers the
#                                                "islands" update backend)
