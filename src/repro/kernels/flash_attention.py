"""Causal GQA flash attention (Pallas, TPU-target).

Streaming-softmax attention: grid (B, H, Sq/bq, Skv/bk) with the running
(max, sum, acc) statistics resident in VMEM across the innermost KV
dimension; fully-masked KV blocks (block start beyond the causal frontier)
are skipped via ``pl.when`` so causal FLOPs are ~halved vs the masked dense
product.  GQA is expressed in the BlockSpec index map (kv head = h // group)
— no KV replication in memory.

Layout: q (B, H, S, D), k/v (B, Hkv, S, D) -> out (B, H, S, D).
Float32 operands contract at full float32 (``Precision.HIGHEST``); Mosaic's
default would contract them in one bfloat16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc, *, scale: float,
            bq: int, bk: int, causal: bool, precision):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    # causal block skip: kv block strictly after the query block's last row
    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0, 0]                                   # (bq, D)
        k = k_ref[0, 0]                                   # (bk, D)
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, precision=precision,
                    preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, precision=precision,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = d ** -0.5 if scale is None else scale
    bq, bk = min(bq, s), min(bk, s)
    assert s % bq == 0 and s % bk == 0

    precision = (jax.lax.Precision.HIGHEST
                 if jnp.result_type(q, k, v) == jnp.float32 else None)
    kern = functools.partial(_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
                             precision=precision)
    return pl.pallas_call(
        kern,
        grid=(b, h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
