"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to "auto": real Mosaic lowering on TPU backends,
interpret mode elsewhere (CPU validation).  The model layer calls these only
when ``cfg.use_flash`` / kernel flags are on; the dry-run lowers the pure-XLA
path so CPU cost_analysis stays well-defined (see DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import pop_adam as _pa
from repro.kernels import pop_matmul as _pm
from repro.kernels import ssd as _ssd
from repro.kernels import wkv6 as _wkv


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("activation", "interpret"))
def pop_matmul(x, w, b=None, *, activation: str = "none", interpret=None):
    return _pm.pop_matmul(x, w, b, activation=activation,
                          interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def pop_adam(params, grads, mu, nu, lr, step, *, interpret=None):
    return _pa.pop_adam(params, grads, mu, nu, lr, step,
                        interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    interpret=None):
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, lw, u, initial_state, *, chunk: int = 64, interpret=None):
    return _wkv.wkv6(r, k, v, lw, u, initial_state, chunk=chunk,
                     interpret=_auto_interpret(interpret))


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, initial_state, *, chunk: int = 128, interpret=None):
    return _ssd.ssd(x, dt, a, b, c, initial_state, chunk=chunk,
                    interpret=_auto_interpret(interpret))


# ---------------------------------------------------------------------------
# dispatch: the model layer's single entry point into the kernel stack
#
# The nn modules (attention/rwkv6/mamba2) keep their pure-jnp reference
# implementations; these dispatchers route the hot op through the Pallas
# kernel when enabled and otherwise call the EXACT nn fallback, so flipping
# the flag never changes off-kernel numerics (tests pin the fallback path
# bitwise).  The kernels carry no custom VJPs, so "auto" (None) resolves to
# kernels only on TPU backends and callers gate them off for differentiated
# (training) forwards.
# ---------------------------------------------------------------------------


def kernels_enabled(flag=None) -> bool:
    """Resolve a tri-state kernel flag: None = auto (TPU backends only)."""
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


def attention_fn(use_kernels=None):
    """An ``attn_fn`` for :func:`repro.nn.attention.gqa_apply` routing
    full-sequence causal attention through the flash kernel — (B,S,H,D)
    nn layout transposed around the kernel's (B,H,S,D) — or None to keep
    the jnp ``sdpa_auto`` path."""
    if not kernels_enabled(use_kernels):
        return None

    def attn(q, k, v, positions, kv_positions, *, causal=True, scale=None):
        b, s, h, _ = q.shape
        if s > 128 and s % 128:  # kernel block constraint: fall back
            from repro.nn.attention import sdpa_auto
            return sdpa_auto(q, k, v, positions, kv_positions, causal=causal,
                             scale=scale)
        y = flash_attention(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                            jnp.moveaxis(v, 1, 2), causal=causal,
                            scale=scale)
        # (B,S,H*D), as sdpa returns it for the output projection
        return jnp.moveaxis(y, 1, 2).reshape(b, s, h * v.shape[-1])

    return attn


def wkv6_apply(r, k, v, lw, u, state, *, use_chunked: bool = True,
               chunk: int = 64, compute_dtype=jnp.float32, use_kernels=None):
    """RWKV6 time-mix scan on the nn layout (r/k/v/lw (B,S,H,D), u (H,D),
    state (B,H,D,D)).  Kernel when enabled and the sequence tiles evenly;
    otherwise the nn chunked/scan selection, verbatim."""
    s = r.shape[1]
    if kernels_enabled(use_kernels) and s % chunk == 0 and s > 1:
        tr = lambda t: jnp.moveaxis(t, 1, 2)
        y, new_state = wkv6(tr(r), tr(k), tr(v), tr(lw), u, state, chunk=chunk)
        return jnp.moveaxis(y, 2, 1), new_state
    from repro.nn import rwkv6 as _nn  # lazy: nn imports this module
    if use_chunked and s % chunk == 0 and s > 1:
        return _nn.wkv6_chunked(r, k, v, lw, u, state, chunk=chunk,
                                compute_dtype=compute_dtype)
    return _nn.wkv6_scan(r, k, v, lw, u, state)


def ssd_apply(x, dt, a, b, c, state, *, use_chunked: bool = True,
              chunk: int = 128, compute_dtype=jnp.float32, use_kernels=None):
    """Mamba2 SSD scan on the nn layout (x (B,S,H,P), dt (B,S,H),
    b/c (B,S,N), state (B,H,P,N)) — kernel or exact nn fallback."""
    s = x.shape[1]
    if kernels_enabled(use_kernels) and s % chunk == 0 and s > 1:
        tr = lambda t: jnp.moveaxis(t, 1, 2)
        y, new_state = ssd(tr(x), tr(dt), a, b, c, state, chunk=chunk)
        return jnp.moveaxis(y, 2, 1), new_state
    from repro.nn import mamba2 as _nn  # lazy: nn imports this module
    if use_chunked and s % chunk == 0 and s > 1:
        return _nn.ssd_chunked(x, dt, a, b, c, state, chunk=chunk,
                               compute_dtype=compute_dtype)
    return _nn.ssd_scan(x, dt, a, b, c, state)
