"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.rwkv6 import wkv6_scan
from repro.nn.mamba2 import ssd_scan


def pop_adam_ref(params, grads, mu, nu, lr, step, *, b1=0.9, b2=0.999,
                 eps=1e-8):
    """(N,P) batched Adam with per-member lr; step is 1-based, () or (N,)."""
    g = grads.astype(jnp.float32)
    mu2 = b1 * mu + (1 - b1) * g
    nu2 = b2 * nu + (1 - b2) * g * g
    stepf = jnp.broadcast_to(step, (params.shape[0],)).astype(jnp.float32)
    c1 = (1 - b1 ** stepf)[:, None]
    c2 = (1 - b2 ** stepf)[:, None]
    upd = lr[:, None] * (mu2 / c1) / (jnp.sqrt(nu2 / c2) + eps)
    return params - upd, mu2, nu2


def pop_matmul_ref(x, w, b=None, *, activation: str = "none"):
    y = jnp.einsum("nbk,nkm->nbm", x, w,
                   preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b[:, None, :].astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "tanh":
        y = jnp.tanh(y)
    return y.astype(x.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        scale: float | None = None):
    """q (B,H,S,D), k/v (B,Hkv,S,D)."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, h // hkv, s, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(b, h, s, d)


def wkv6_ref(r, k, v, lw, u, initial_state):
    """(B,H,S,D) layout -> matches kernels.wkv6.wkv6 outputs (fp32)."""
    to_bshd = lambda t: jnp.moveaxis(t, 1, 2)
    y, s = wkv6_scan(to_bshd(r).astype(jnp.float32),
                     to_bshd(k).astype(jnp.float32),
                     to_bshd(v).astype(jnp.float32),
                     to_bshd(lw).astype(jnp.float32),
                     u.astype(jnp.float32),
                     initial_state.astype(jnp.float32))
    return jnp.moveaxis(y, 2, 1), s


def ssd_ref(x, dt, a, b, c, initial_state):
    """The nn layout (x (B,S,H,P)) in fp32 -> matches kernels.ssd.ssd."""
    return ssd_scan(*(t.astype(jnp.float32)
                      for t in (x, dt, a, b, c, initial_state)))
