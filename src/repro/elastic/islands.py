"""The ``"islands"`` update backend: member groups shard_mapped over islands.

``backend="sharded"`` lets GSPMD propagate a population sharding through
the jitted vmapped update; this backend makes the paper's §5.1 topology
*explicit* instead: the population axis is split over the ``"pop"`` mesh
axis of an :class:`~repro.elastic.layout.IslandLayout` with
``jax.shard_map``, so each island runs a plain vectorized update
over only its own member group and NO cross-island communication exists in
the update step at all (members are independent; the only collectives in
island training are the PBT gathers at evolve time).

Registered under ``"islands"`` in the ``repro.pop`` backend registry, so it
is the same one-line config swap as the other three:

    PopulationConfig(size=8, backend="islands")

Update numerics are identical to ``backend="vectorized"`` — the tests
assert it — because sharding only decides *where* each member's update
runs, never what it computes.
"""
from __future__ import annotations

from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from repro.pop.backend import register_backend


def _build_islands(agent, num_steps: int, donate: bool, mesh=None):
    if agent.population_level:
        raise ValueError("islands backend requires per-member agents (a "
                         "shared critic is replicated, not split over "
                         "islands)")
    from repro.core.vectorize import chain_steps
    batch_axis = 0 if num_steps == 1 else 1

    fused_fn = (agent.fused_update()
                if getattr(agent, "fused_adam", False) else None)
    if fused_fn is not None:
        # population-level update over the island's OWN member group: under
        # shard_map the local shard is just a smaller population, so the
        # fused pop_adam path shards over "pop" unchanged
        pop_inner = (fused_fn if num_steps == 1
                     else chain_steps(fused_fn, num_steps))

        def local(pop_state, batches, hypers):
            return pop_inner(pop_state, batches, hypers)
    else:
        inner = (agent.update if num_steps == 1
                 else chain_steps(agent.update, num_steps))

        def local(pop_state, batches, hypers):
            # ONE island's body: vectorized update over its own member group
            if hypers is None:
                return jax.vmap(lambda s, b: inner(s, b, None),
                                in_axes=(0, batch_axis))(pop_state, batches)
            return jax.vmap(inner, in_axes=(0, batch_axis, 0))(
                pop_state, batches, hypers)

    state_spec = P("pop")
    batch_spec = P("pop") if num_steps == 1 else P(None, "pop")
    compiled = {}

    def resolve_mesh(pop_state):
        if mesh is not None:
            return mesh
        from repro.elastic.layout import plan_layout
        n = jax.tree.leaves(pop_state)[0].shape[0]
        return plan_layout(len(jax.devices()), n).mesh

    def stepped(pop_state, batches, hypers=None):
        m = resolve_mesh(pop_state)
        # with a non-trivial (data, model) grid inside each island, a
        # shard_map over "pop" alone would *replicate* the intra-island
        # axes and ignore the model-sharded parameter placement; run the
        # population-level body under plain jit instead and let GSPMD
        # propagate the placed input shardings (see IslandLayout.place
        # model_rules).
        gspmd = m.devices.size > m.shape.get("pop", m.devices.size)
        key = (id(m), hypers is None, gspmd)
        fn = compiled.get(key)
        if fn is None:
            if gspmd:
                body = (partial(local, hypers=None) if hypers is None
                        else local)
            elif hypers is None:
                body = jax.shard_map(
                    lambda s, b: local(s, b, None), mesh=m,
                    in_specs=(state_spec, batch_spec),
                    out_specs=(state_spec, state_spec), check_vma=False)
            else:
                body = jax.shard_map(
                    local, mesh=m,
                    in_specs=(state_spec, batch_spec, state_spec),
                    out_specs=(state_spec, state_spec), check_vma=False)
            fn = compiled[key] = jax.jit(
                body, donate_argnums=(0,) if donate else ())
        if hypers is None:
            return fn(pop_state, batches)
        return fn(pop_state, batches, hypers)

    return stepped


register_backend("islands", _build_islands)
