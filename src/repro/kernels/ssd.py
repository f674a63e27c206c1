"""Mamba2 SSD chunked-scan Pallas kernel.

Mirrors ``repro.nn.mamba2.ssd_chunked``: grid (B, H, S/chunk), the (P x N)
SSM state carried in VMEM across chunks; each program computes the
intra-chunk quadratic term (segsum decay) plus the inter-chunk state
contribution, then advances the state.

Layout: x (B,H,S,P), dt (B,H,S), b/c (B,S,N) (shared across heads — the
index map ignores h), a (H,), initial state (B,H,P,N).  dt enters the
kernel as a (B,H,S,1) column, so its block meets the TPU's (8, 128) tiling
rule for any chunk that is a multiple of 8, and ``a`` sits whole in SMEM,
read per head.  Every contraction runs at full float32
(``Precision.HIGHEST``); Mosaic's default would contract float32 in one
bfloat16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
GRID = 1024.0


def _cumsum(v, rows, cols):
    """Inclusive cumsum of a (CL, 1) column as a (1, CL) row, a (CL, 1)
    column and the (1, 1) total.  Mosaic has neither cumsum nor a
    (CL,1)->(1,CL) relayout, so all three are masked reductions."""
    row = jnp.sum(jnp.where(rows <= cols, v, 0.0), axis=0, keepdims=True)
    col = jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1, keepdims=True)
    return row, col, jnp.sum(v, axis=0, keepdims=True)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, sout_ref,
            state, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)        # (CL, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (CL, 1)
    a = a_ref[pl.program_id(1)]                # scalar (SMEM)
    bb = b_ref[0].astype(jnp.float32)          # (CL, N)
    cc = c_ref[0].astype(jnp.float32)          # (CL, N)

    n = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tril = rows >= cols
    lda = dt * a                               # (CL, 1), <= 0
    dt_row = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=0,
                     keepdims=True)            # (1, CL)
    # the segsum is a difference of cumsums that reach hundreds over a
    # chunk of 256, which float32 rounds to ~1e-4: split each log-decay
    # into a part on a 2**-10 grid, whose cumsums are exact while below
    # 2**14, and a remainder under 2**-11, and difference them apart
    hi = jnp.floor(lda * GRID + 0.5) / GRID
    (hr, hc, ht), (lr, lc, lt) = (_cumsum(v, rows, cols)
                                  for v in (hi, lda - hi))
    ca, ca_tot = hc + lc, ht + lt              # (CL, 1), (1, 1)

    seg = (hc - hr) + (lc - lr)
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)
    cb = jnp.dot(cc, bb.T, precision=HIGHEST,
                 preferred_element_type=jnp.float32)         # (CLt, CLs)
    m = cb * decay * dt_row
    y = jnp.dot(m, x, precision=HIGHEST, preferred_element_type=jnp.float32)

    st = state[...]                            # (P, N)
    y = y + jnp.exp(ca) * jnp.dot(
        cc, st.T, precision=HIGHEST, preferred_element_type=jnp.float32)
    w_out = jnp.exp((ht - hc) + (lt - lc)) * dt    # (CL, 1)
    state[...] = jnp.exp(ca_tot) * st + jnp.dot(
        (x * w_out).T, bb, precision=HIGHEST,
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        sout_ref[0, 0] = state[...].astype(sout_ref.dtype)


def ssd(x, dt, a, b, c, initial_state, *, chunk: int = 128,
        interpret: bool = False):
    """x: (B,H,S,P); dt: (B,H,S); a: (H,); b/c: (B,S,N); state: (B,H,P,N)."""
    bsz, h, s, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0

    kern = functools.partial(_kernel, chunk=chunk)
    y, sout = pl.pallas_call(
        kern,
        grid=(bsz, h, s // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd",
    )(x, dt[..., None], a.astype(jnp.float32), b, c, initial_state)
    return y, sout
