"""RWKV6 WKV recurrence Pallas kernel (chunked linear-attention form).

Mirrors ``repro.nn.rwkv6.wkv6_chunked``: grid (B, H, S/chunk) with the
(Dk x Dv) state resident in VMEM across the chunk dimension (innermost), so
HBM traffic is O(S*D) instead of the O(S*D^2) a naive scan materializes.
All decay exponents are <= 0 (log-space cumsums) — no overflow.

Layout: r/k/v/lw (B, H, S, D) (pre-transposed by ops.py), u (H, D),
initial state (B, H, Dk, Dv) -> y (B, H, S, D), final state.  Every
contraction runs at full float32 (``Precision.HIGHEST``); Mosaic's default
would contract float32 in one bfloat16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
            state, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (CL, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)               # (1, D)

    n = r.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum)
    cl_cum = jnp.dot((rows >= cols).astype(jnp.float32), lw,
                     precision=HIGHEST, preferred_element_type=jnp.float32)
    cl_prev = cl_cum - lw
    cl_tot = cl_cum[-1:]

    r_in = r * jnp.exp(cl_prev)
    k_out = k * jnp.exp(cl_tot - cl_cum)

    expo = cl_prev[:, None, :] - cl_cum[None, :, :]           # (CL,CL,D)
    d = r.shape[1]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (n, n, d), 0) >
            jax.lax.broadcasted_iota(jnp.int32, (n, n, d), 1))
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, expo, 0.0)), 0.0)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)   # (CL,CL)
    diag = jnp.sum(r * u * k, axis=-1)
    a = a + jnp.eye(n, dtype=a.dtype) * diag[:, None]

    st = state[...]
    y = jnp.dot(r_in, st, precision=HIGHEST,
                preferred_element_type=jnp.float32) + \
        jnp.dot(a, v, precision=HIGHEST, preferred_element_type=jnp.float32)
    state[...] = jnp.exp(cl_tot[0])[:, None] * st + jnp.dot(
        k_out.T, v, precision=HIGHEST, preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        sout_ref[0, 0] = state[...].astype(sout_ref.dtype)


def wkv6(r, k, v, lw, u, initial_state, *, chunk: int = 64,
         interpret: bool = False):
    """r/k/v/lw: (B, H, S, D); u: (H, D); initial_state: (B, H, D, D)."""
    b, h, s, d = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0

    kern = functools.partial(_kernel, chunk=chunk)
    io_spec = pl.BlockSpec((1, 1, chunk, d), lambda bi, hi, ci: (bi, hi, ci, 0))
    y, sout = pl.pallas_call(
        kern,
        grid=(b, h, s // chunk),
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, 1, d), lambda bi, hi, ci: (hi, 0, 0)),
                  pl.BlockSpec((1, 1, d, d), lambda bi, hi, ci: (bi, hi, 0, 0))],
        out_specs=[io_spec,
                   pl.BlockSpec((1, 1, d, d), lambda bi, hi, ci: (bi, hi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
        name="wkv6",
    )(r, k, v, lw, u.reshape(h, 1, d), initial_state)
    return y, sout
