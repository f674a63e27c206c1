"""Population-batched matmul Pallas kernel — the paper's core compute shape.

The paper's protocol turns N per-member small matmuls (too small to saturate
anything) into ONE batched launch.  On TPU the natural mapping is: the
population axis becomes the outer grid dimension, and each (member, row-tile,
col-tile) program runs an MXU-aligned (bm x bk)@(bk x bn) accumulation with
the accumulator resident in VMEM.  ``vmap``-of-matmul gives XLA the same
opportunity; this kernel makes the tiling explicit (and fuses the bias +
activation epilogue, which XLA sometimes leaves unfused for tiny matmuls).

Layout: x (N, B, K), w (N, K, M), optional bias (N, M) -> y (N, B, M).
The bias enters the kernel as (N, 1, M), so its block's last two dims are
(1, bn) against (1, M) and meet the TPU's (8, 128) tiling rule.
Grid: (N, B/bm, M/bn, K/bk), K innermost so the VMEM accumulator carries.
Float32 operands contract at full float32 (``Precision.HIGHEST``); Mosaic's
default would contract them in one bfloat16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, b_ref, o_ref, acc, *, activation: str,
            precision):
    @pl.when(pl.program_id(3) == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[0], w_ref[0], precision=precision,
                        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _():
        y = acc[...]
        if b_ref is not None:
            y = y + b_ref[0].astype(jnp.float32)
        if activation == "relu":
            y = jnp.maximum(y, 0.0)
        elif activation == "tanh":
            y = jnp.tanh(y)
        o_ref[0] = y.astype(o_ref.dtype)


def supports_shapes(bsz: int, k: int, m: int, *, bm: int = 128,
                    bn: int = 128, bk: int = 128) -> bool:
    """Whether :func:`pop_matmul` can tile ``(N,bsz,k) @ (N,k,m)``.

    Blocks clamp to the problem, so each dimension must either fit inside
    one block or be a multiple of the block.  ``repro.rl.networks`` consults
    this before routing a population-batched linear through the kernel, so
    odd hidden sizes fall back to the jnp path instead of asserting."""
    if min(bsz, k, m) <= 0:
        return False
    return all(d % min(blk, d) == 0
               for d, blk in ((bsz, bm), (m, bn), (k, bk)))


def pop_matmul(x, w, b=None, *, activation: str = "none",
               bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool = False):
    """y[n] = act(x[n] @ w[n] + b[n]).  Block sizes clamp to the problem."""
    n, bsz, k = x.shape
    m = w.shape[-1]
    bm, bn, bk = min(bm, bsz), min(bn, m), min(bk, k)
    assert bsz % bm == 0 and m % bn == 0 and k % bk == 0, \
        f"tile mismatch: {(bsz, m, k)} vs {(bm, bn, bk)}"

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, j, l, kk: (i, j, kk)),
        pl.BlockSpec((1, bk, bn), lambda i, j, l, kk: (i, kk, l)),
    ]
    args = [x, w]
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.result_type(x, w) == jnp.float32 else None)
    if b is not None:
        in_specs.append(pl.BlockSpec((1, 1, bn),
                                     lambda i, j, l, kk: (i, 0, l)))
        args.append(b.reshape(n, 1, m))
        kern = functools.partial(_kernel, activation=activation,
                                 precision=precision)
    else:
        kern = functools.partial(
            lambda xr, wr, orf, acc, **kw: _kernel(xr, wr, None, orf, acc,
                                                   **kw),
            activation=activation, precision=precision)

    return pl.pallas_call(
        kern,
        grid=(n, bsz // bm, m // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, l, kk: (i, j, l)),
        out_shape=jax.ShapeDtypeStruct((n, bsz, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="pop_matmul",
    )(*args)
