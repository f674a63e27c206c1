"""Data-parallel gradient reduction with int8 error-feedback compression.

``compressed_psum_tree`` runs inside ``shard_map`` over the data axis: each
rank quantizes its local gradient to int8 (+ one fp32 scale per tensor),
all-gathers the int8 payloads (wire bytes = N x size x 1B instead of the
~2 x size x 4B of a ring fp32 all-reduce), decompresses and sums locally.
Quantization error is fed back into the next step (error feedback keeps
Adam/SGD convergence — Karimireddy et al., 2019; validated in
tests/test_checkpoint_optim.py and tests/test_dp_compression.py).

``make_dp_update`` wraps a single-rank update_fn into a shard_map'd
data-parallel update with either plain psum or compressed reduction —
selected by ``TrainConfig.grad_compression``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.optim.compress import compress_tree, decompress_tree


def compressed_psum_tree(grads, error, axis: str):
    """Inside shard_map: returns (mean_grads, new_error)."""
    q, s, new_error = compress_tree(grads, error)
    n = jax.lax.psum(1, axis)

    def reduce_one(qi, si):
        gq = jax.lax.all_gather(qi, axis)            # (N, ...) int8
        gs = jax.lax.all_gather(si, axis)            # (N,) fp32
        return jnp.tensordot(gs, gq.astype(jnp.float32), axes=(0, 0)) / n

    mean = jax.tree.map(reduce_one, q, s)
    return mean, new_error


def plain_psum_tree(grads, axis: str):
    n = jax.lax.psum(1, axis)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis) / n, grads)


def make_dp_update(grad_fn, opt_update, mesh, *, axis: str = "data",
                   compression: str = "none"):
    """grad_fn(params, batch) -> (loss, grads) computed on the local shard.

    Returns ``update(params, opt_state, error, batch) ->
    (params, opt_state, error, loss)`` with params replicated and the batch
    sharded over ``axis``.
    """
    from repro.optim import apply_updates

    def local_update(params, opt_state, error, batch):
        loss, grads = grad_fn(params, batch)
        if compression == "int8":
            grads, error = compressed_psum_tree(grads, error, axis)
        else:
            grads = plain_psum_tree(grads, axis)
        updates, opt_state = opt_update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, error, jax.lax.pmean(loss, axis)

    spec_rep = P()
    spec_data = P(axis)
    return jax.jit(jax.shard_map(
        local_update, mesh=mesh,
        in_specs=(spec_rep, spec_rep, spec_rep, spec_data),
        out_specs=(spec_rep, spec_rep, spec_rep, spec_rep),
        check_vma=False))
