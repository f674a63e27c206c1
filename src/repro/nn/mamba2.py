"""Mamba-2 blocks (state-space duality / SSD), used by zamba2-7b and
granite-4.0-h-micro.

Recurrence per head (head dim P, state dim N):
    h_t = exp(a * dt_t) h_{t-1} + dt_t * x_t B_t^T        h: (P, N)
    y_t = h_t C_t + D x_t

Two implementations:
  * ``ssd_scan``    — literal recurrence (oracle + decode step)
  * ``ssd_chunked`` — chunk-parallel SSD form (intra-chunk quadratic term +
    inter-chunk state scan).  Mirrored by the Pallas kernel in
    ``repro/kernels/ssd.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.sharding import constrain
from repro.nn.basic import lecun_normal, normal_init, rmsnorm_init


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def ssd_scan(x, dt, a, b, c, state):
    """x: (B,S,H,P); dt: (B,S,H); a: (H,); b/c: (B,S,N) (single group);
    state: (B,H,P,N).  Returns (y (B,S,H,P), final_state)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp            # (B,H,P), (B,H), (B,N), (B,N)
        da = jnp.exp(dt_t * a)               # (B,H)
        h = da[..., None, None] * h + jnp.einsum(
            "bh,bhp,bn->bhpn", dt_t, x_t, b_t)
        y = jnp.einsum("bhpn,bn->bhp", h, c_t)
        return h, y
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0))
    final, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssd_chunked(x, dt, a, b, c, state, *, chunk: int = 128,
                compute_dtype=jnp.float32):
    """Chunk-parallel SSD, equal to ``ssd_scan`` in fp32. S % chunk == 0.

    ``compute_dtype=bf16`` runs the intra-chunk quadratic term (the HBM-
    traffic hot spot — a (B,NC,CL,CL,H) tensor) in bf16 while keeping the
    state recurrence and decay cumsums in fp32 (§Perf zamba2 iteration)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc, cl = s // chunk, chunk

    # ALL chunk math lives inside the scan body (per-chunk slices), mirroring
    # the Pallas kernel: with scan-over-layers remat, the backward pass then
    # recomputes only chunk i's work at inner step i.  (Computing the
    # intra-chunk terms vectorized over NC *outside* the scan made remat
    # replay full-sequence tensors once per inner step — a ~NC x traffic
    # blowup measured in §Perf zamba2 iteration 1.)
    cd = compute_dtype
    tril = jnp.tril(jnp.ones((cl, cl), bool))[:, :, None]

    @jax.checkpoint
    def body(h0, inp):
        xc, dtc, bc, cc = inp                        # (B,CL,H,P)/(B,CL,H)/(B,CL,N)
        lda = dtc * a                                # (B,CL,H), <= 0
        ca = jnp.cumsum(lda, axis=1)
        ca_total = ca[:, -1:]                        # (B,1,H)

        # intra-chunk: M[t,s] = exp(ca_t - ca_s) (C_t.B_s) dt_s  for s <= t
        seg = ca[:, :, None] - ca[:, None, :]        # (B,CLt,CLs,H)
        decay = jnp.exp(jnp.where(tril, seg, -jnp.inf)).astype(cd)
        cb = jnp.einsum("btm,bsm->bts", cc.astype(cd), bc.astype(cd),
                        preferred_element_type=cd)
        m = cb[..., None] * decay * dtc[:, None].astype(cd)
        y = jnp.einsum("btsh,bshp->bthp", m, xc.astype(cd),
                       preferred_element_type=jnp.float32)
        # contribution of the incoming state + state advance
        y = y + jnp.einsum("bth,bhpn,btn->bthp", jnp.exp(ca), h0, cc)
        w_out = (jnp.exp(ca_total - ca) * dtc).astype(cd)
        h0 = jnp.exp(ca_total)[:, 0][..., None, None] * h0 + jnp.einsum(
            "bsh,bshp,bsm->bhpm", w_out, xc.astype(cd), bc.astype(cd),
            preferred_element_type=jnp.float32)
        return h0, y

    xs = (jnp.moveaxis(x.reshape(bsz, nc, cl, h, p), 1, 0),
          jnp.moveaxis(dt.reshape(bsz, nc, cl, h), 1, 0),
          jnp.moveaxis(b.reshape(bsz, nc, cl, n), 1, 0),
          jnp.moveaxis(c.reshape(bsz, nc, cl, n), 1, 0))
    final, ys = jax.lax.scan(body, state, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p), final


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def mamba2_block_init(key, *, d_model: int, d_state: int = 64,
                      head_dim: int = 64, expand: int = 2,
                      conv_kernel: int = 4):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_ch = d_inner + 2 * d_state
    k_in, k_conv, k_out, k_dt = jax.random.split(key, 4)
    d_in_proj = 2 * d_inner + 2 * d_state + n_heads
    return {
        "in_proj": {"w": lecun_normal(k_in, (d_model, d_in_proj))},
        "conv": {"w": normal_init(k_conv, (conv_kernel, conv_ch), std=0.1),
                 "b": jnp.zeros((conv_ch,), jnp.float32)},
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(  # softplus^-1 of U(1e-3, 1e-1) midpoints
            jnp.linspace(1e-3, 1e-1, n_heads))),
        "norm": rmsnorm_init(d_inner),
        "out_proj": {"w": lecun_normal(k_out, (d_inner, d_model))},
    }


def mamba2_init_state(batch: int, d_model: int, *, d_state: int = 64,
                      head_dim: int = 64, expand: int = 2,
                      conv_kernel: int = 4, dtype=jnp.float32):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_ch = d_inner + 2 * d_state
    return {
        "ssm": jnp.zeros((batch, n_heads, head_dim, d_state), jnp.float32),
        "conv": jnp.zeros((batch, conv_kernel - 1, conv_ch), dtype),
    }


def _causal_conv(w, bias, x, x_prev):
    """Depthwise causal conv. x: (B,S,C); x_prev: (B,K-1,C) left context."""
    k = w.shape[0]
    xp = jnp.concatenate([x_prev.astype(x.dtype), x], axis=1)
    y = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),                 # (K, I=1, C)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=w.shape[1])
    return y + bias.astype(x.dtype), xp[:, -(k - 1):]


def mamba2_block_apply(p, x, state, *, d_state: int = 64, head_dim: int = 64,
                       expand: int = 2, use_chunked: bool = True,
                       chunk: int = 128, compute_dtype=jnp.float32,
                       norm_eps: float, use_kernels=None):
    """x: (B,S,D); state from ``mamba2_init_state``. Returns (y, new_state).
    ``norm_eps`` is the gated RMSNorm's epsilon."""
    bsz, s, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // head_dim

    zxbcdt = x @ p["in_proj"]["w"].astype(x.dtype)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + d_inner + 2 * d_state]
    dt_raw = zxbcdt[..., -n_heads:]

    xbc, conv_state = _causal_conv(p["conv"]["w"], p["conv"]["b"], xbc,
                                   state["conv"])
    xbc = jax.nn.silu(xbc)
    xh = xbc[..., :d_inner].reshape(bsz, s, n_heads, head_dim)
    b = xbc[..., d_inner:d_inner + d_state]
    c = xbc[..., d_inner + d_state:]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])

    # sequence-parallel -> head-parallel relayout ONCE per layer, so the
    # chunk scan never slices a model-sharded sequence axis (that put a
    # collective inside every scan step — §Perf zamba2 iteration 3).
    xh = constrain(xh, "F", None, "M", None)
    dt = constrain(dt, "F", None, "M")
    b = constrain(b, "F", None, None)
    c = constrain(c, "F", None, None)

    x32, b32, c32 = (t.astype(jnp.float32) for t in (xh, b, c))
    from repro.kernels.ops import ssd_apply  # lazy: ops falls back to us
    with jax.named_scope("ssd"):
        y, ssm = ssd_apply(x32, dt, a, b32, c32, state["ssm"],
                           use_chunked=use_chunked, chunk=chunk,
                           compute_dtype=compute_dtype,
                           use_kernels=use_kernels)
    y = y + p["d_skip"][:, None] * x32
    y = constrain(y, "F", None, "M", None)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)

    # gated RMSNorm then out-projection
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + norm_eps)
         * p["norm"]["scale"]).astype(x.dtype)
    return y @ p["out_proj"]["w"].astype(x.dtype), {"ssm": ssm, "conv": conv_state}
