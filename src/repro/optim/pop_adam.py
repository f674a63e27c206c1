"""Population-level Adam: the ``kernels/pop_adam`` Pallas kernel as an
optimizer.

The stock path applies :func:`repro.optim.adam` per member under ``vmap``,
which leaves XLA to emit one elementwise chain per pytree leaf per member.
This module exposes the alternative the kernel was written for: flatten the
population's parameters to ONE ``(N, P)`` matrix and update every member's
Adam state in a single fused pass, with the per-member learning rate (the
paper's vmapped-hyperparameter protocol) read per grid row.

Opt-in and TPU-gated: ``fused=None`` ("auto") lowers the Pallas kernel only
on TPU backends and otherwise falls back to the stock per-member optimizer
under ``vmap`` — literally ``repro.optim.adam``, so bitwise equality with
the agents' own update path holds by construction
(``tests/test_experience_ppo.py`` and ``tests/test_lm_population.py`` pin
it).  A flattened re-derivation of the same expressions is NOT bitwise-safe
off-TPU: XLA CPU duplicates the moment mul-adds into the parameter-update
fusion and FMA-contracts them differently per program (1-2 ulp).
``fused=True`` forces the kernel (interpret mode off-TPU — CPU validation
only).

State compatibility: ``init_fn`` produces the same ``AdamState`` structure
as ``jax.vmap(stock_init)`` (step ``(N,)``, mu/nu stacked trees), so
checkpoints, elastic resize and the gated-update bookkeeping in
``repro.core.shared`` are oblivious to which path is active.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.optim.optimizers import AdamState


def _flatten(tree):
    """Stacked tree (leaves (N, ...)) -> ((N, P) f32, rebuild fn)."""
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    sizes = [math.prod(l.shape[1:]) for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1)

    def rebuild(mat, like=None):
        outs, off = [], 0
        ref = leaves if like is None else jax.tree.leaves(like)
        for leaf, size in zip(ref, sizes):
            outs.append(mat[:, off:off + size]
                        .reshape(leaf.shape).astype(leaf.dtype))
            off += size
        return jax.tree.unflatten(treedef, outs)

    return flat, rebuild


def _use_kernel(fused) -> bool:
    if fused is None:
        return jax.default_backend() == "tpu"
    return bool(fused)


def _clip_stacked(grads, max_norm):
    """Per-member global-norm clip on a stacked tree — the exact lowering of
    ``jax.vmap(clip_by_global_norm)``: per-leaf square-sums over the non-pop
    axes, python-summed in ``jax.tree.leaves`` order, one sqrt, then an
    elementwise scale of every leaf."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)),
                axis=tuple(range(1, x.ndim))) for x in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(
        lambda x: x * scale.reshape(scale.shape + (1,) * (x.ndim - 1)),
        grads)


def population_adam(lr: float = 3e-4, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, weight_decay: float = 0.0,
                    max_grad_norm=None, block: int = 4096, fused=None):
    """Build ``(init_fn, apply_fn)`` over population-stacked pytrees.

        state = init_fn(stacked_params)            # leaves (N, ...)
        params, state = apply_fn(params, grads, state, lr_override=...)

    ``lr_override`` may be a scalar or an ``(N,)`` per-member vector, as may
    ``wd_override`` (a traced per-member decoupled weight decay — the LM
    path's PBT hyper).  ``weight_decay``/``max_grad_norm`` mirror
    :func:`repro.optim.adam` so the fused path stays bitwise-equal to the
    stock optimizer under vmap.  Unlike the stock pair this applies the
    update internally (the kernel fuses moment update + bias correction +
    apply in one pass).
    """
    kernel = _use_kernel(fused)

    def init_fn(params):
        n = jax.tree.leaves(params)[0].shape[0]
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
        return AdamState(step=jnp.zeros((n,), jnp.int32),
                         mu=zeros(), nu=zeros())

    def apply_fn(params, grads, state, lr_override=None, wd_override=None):
        n = jax.tree.leaves(params)[0].shape[0]
        lr_t = lr if lr_override is None else lr_override
        lr_vec = jnp.broadcast_to(jnp.asarray(lr_t, jnp.float32), (n,))
        wd = weight_decay if wd_override is None else wd_override
        decoupled = (wd_override is not None) or bool(weight_decay)

        if not kernel:
            # off-TPU fallback: stock adam under vmap, LITERALLY — reusing
            # the stock update_fn per member makes bitwise equality with
            # the agents' optax-style path true by construction.  A
            # flattened (N, P) re-derivation of the same expressions is
            # NOT bitwise-safe: XLA CPU duplicates the moment mul-adds
            # into the parameter-update fusion and FMA-contracts them
            # differently per program (1-2 ulp on this config).
            from repro.optim.optimizers import adam as _stock_adam
            from repro.optim.optimizers import apply_updates
            _, stock_upd = _stock_adam(lr, b1, b2, eps,
                                       weight_decay=weight_decay,
                                       max_grad_norm=max_grad_norm)
            wd_vec = None if not decoupled else \
                jnp.broadcast_to(jnp.asarray(wd, jnp.float32), (n,))

            def member(p, g, m, v, s, lr_i, wd_i=None):
                st = AdamState(step=s, mu=m, nu=v)
                u, st2 = stock_upd(g, st, p, lr_override=lr_i,
                                   wd_override=wd_i)
                return apply_updates(p, u), st2

            if wd_vec is None:
                p2, new_state = jax.vmap(member)(
                    params, grads, state.mu, state.nu, state.step, lr_vec)
            else:
                p2, new_state = jax.vmap(member)(
                    params, grads, state.mu, state.nu, state.step, lr_vec,
                    wd_vec)
            return p2, new_state

        if max_grad_norm is not None:
            grads = _clip_stacked(grads, max_grad_norm)
        step = state.step + 1

        pf, rebuild = _flatten(params)
        gf, _ = _flatten(grads)
        mf, _ = _flatten(state.mu)
        nf, _ = _flatten(state.nu)

        from repro.kernels.pop_adam import pop_adam as _pa
        p2, m2, v2 = _pa(pf, gf, mf, nf, lr_vec, step, b1=b1, b2=b2,
                         eps=eps, block=block,
                         interpret=jax.default_backend() != "tpu")
        if decoupled:
            # the kernel has no decay term; post-apply it (kernel mode
            # is numerics-checked against the fallback, not bitwise)
            wd_vec = jnp.broadcast_to(jnp.asarray(wd, jnp.float32), (n,))
            p2 = p2 - (lr_vec * wd_vec)[:, None] * pf

        new_state = AdamState(step=step, mu=rebuild(m2, state.mu),
                              nu=rebuild(v2, state.nu))
        return rebuild(p2), new_state

    return init_fn, apply_fn
