"""Fused population-Adam Pallas kernel.

The paper's protocol makes the *optimizer* update the second compute hot
spot after the matmuls: N members' Adam states update elementwise every
step.  XLA emits one elementwise chain per leaf per member; this kernel
fuses the whole thing over flattened member parameters with the
PER-MEMBER learning rate (the vmapped-hyperparameter protocol) broadcast
down each member's row.

Layout: params/grads/mu/nu (N, P) fp32, lr (N,), step (N,) — the step is
per member because gated update schemes (CEM-RL's train_frac, TD3's
delayed actor) legitimately let members' optimizer clocks diverge.  Each
program updates a (rows, lanes) tile: members ride the sublanes and
parameters the lanes, which keeps every block inside the TPU's (8, 128)
tiling rule (a block's last two dims are multiples of (8, 128) or equal to
the array's).  ``rows`` is 8 when 8 divides N and all of N otherwise; the
lane block shrinks as the rows grow so that the seven double-buffered
tiles stay within a fixed VMEM budget: for every N that 8 divides, and for
other N up to 1168 members (past that the 128-lane floor exceeds the
budget).  The per-member
scalars (lr and the two bias corrections) enter as (N, 1) columns,
computed outside the kernel on (N,) vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# bytes of VMEM for the kernel's tiles: 7 arrays (4 in, 3 out), each
# double-buffered; v5e's default scoped VMEM limit is 16 MiB
VMEM_BUDGET = 8 << 20


def tiles(n: int, p: int, block: int = 4096) -> tuple[int, int]:
    """(rows, lanes) of one program's tile for an (n, p) population."""
    rows = 8 if n % 8 == 0 else n
    padded = -(-rows // 8) * 8                 # sublanes a tile occupies
    fit = VMEM_BUDGET // (7 * 2 * 4 * padded) // 128 * 128
    return rows, min(block, max(fit, 128), p)


def _kernel(lr_ref, c1_ref, c2_ref, p_ref, g_ref, mu_ref, nu_ref,
            po_ref, muo_ref, nuo_ref, *, b1: float, b2: float, eps: float):
    g = g_ref[...].astype(jnp.float32)
    mu = b1 * mu_ref[...] + (1.0 - b1) * g
    nu = b2 * nu_ref[...] + (1.0 - b2) * g * g
    upd = lr_ref[...] * (mu / c1_ref[...]) / (
        jnp.sqrt(nu / c2_ref[...]) + eps)
    po_ref[...] = p_ref[...] - upd
    muo_ref[...] = mu
    nuo_ref[...] = nu


def pop_adam(params, grads, mu, nu, lr, step, *, b1: float = 0.9,
             b2: float = 0.999, eps: float = 1e-8, block: int = 4096,
             interpret: bool = False):
    """params/grads/mu/nu: (N, P); lr: (N,); step: () or (N,) int32
    (1-based; a scalar broadcasts to every member).  ``block`` caps the
    lane block; P need not be a multiple of it (the tail is zero-padded).

    Returns (new_params, new_mu, new_nu)."""
    n, p = params.shape
    rows, lanes = tiles(n, p, block)
    pad = (-p) % lanes
    if pad:
        params, grads, mu, nu = (jnp.pad(x, ((0, 0), (0, pad)))
                                 for x in (params, grads, mu, nu))
    stepf = jnp.broadcast_to(step, (n,)).astype(jnp.float32)
    col = lambda v: v.astype(jnp.float32).reshape(n, 1)
    kern = functools.partial(_kernel, b1=b1, b2=b2, eps=eps)
    tile = pl.BlockSpec((rows, lanes), lambda i, j: (i, j))
    member = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        kern,
        grid=(n // rows, (p + pad) // lanes),
        in_specs=[member, member, member,                  # lr, c1, c2
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n, p + pad), jnp.float32)] * 3,
        interpret=interpret,
        name="pop_adam",
    )(col(lr), col(1.0 - b1 ** stepf), col(1.0 - b2 ** stepf),
      params, grads, mu, nu)
    return tuple(x[:, :p] for x in out) if pad else tuple(out)
