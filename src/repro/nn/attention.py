"""Attention blocks: GQA (grouped-query) and MLA (DeepSeek multi-head latent).

Both support three execution modes through one code path:
  * full-sequence training / prefill  (q_len == kv_len, causal)
  * incremental decode with a KV cache (q_len == 1, kv_len == cache size)

Caches are plain dicts of arrays so they shard with ordinary
``NamedSharding``s: GQA caches (k, v) of shape (B, S, H_kv, D); MLA caches
the *compressed* latent (B, S, kv_lora) + shared rope key (B, S, rope_dim),
which is the MLA memory win and is what we shard over the mesh.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.sharding import constrain
from repro.nn.basic import lecun_normal, rmsnorm_init, rmsnorm_apply
from repro.nn.rotary import apply_rope

BIG_NEG = -2.0e38  # mask value in fp32 softmax


def _heads_divide_model(num_heads: int) -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return False
    return num_heads % mesh.shape["model"] == 0


# ---------------------------------------------------------------------------
# core scaled-dot-product attention (XLA path; the Pallas flash kernel in
# repro/kernels mirrors this math — see kernels/ref.py)
# ---------------------------------------------------------------------------


def sdpa(q, k, v, q_positions, kv_positions, *, causal: bool = True, scale: float):
    """q: (B,Sq,H,D), k/v: (B,Skv,Hkv,D) with H % Hkv == 0. fp32 softmax."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    q = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_positions[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
        logits = jnp.where(mask, logits, BIG_NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h * v.shape[-1])


Q_CHUNK = 512  # query-block size for the chunked (flash-style) XLA path


def sdpa_chunked(q, k, v, q_positions, kv_positions, *, causal: bool = True,
                 scale: float, chunk: int = Q_CHUNK):
    """Query-chunked attention: O(chunk * S) score memory instead of O(S^2).

    This is the XLA analogue of the Pallas flash kernel's outer loop (the
    kernel additionally streams KV through VMEM and skips fully-masked KV
    blocks); it is what makes the 32k prefill cells fit in HBM on the
    dry-run baseline.  Each chunk body is rematerialized so the backward
    pass stores only per-chunk outputs.
    """
    b, s, h, d = q.shape
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, h, d), 1, 0)
    pc = jnp.moveaxis(q_positions.reshape(b, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(_, xs):
        qi, pi = xs
        return None, sdpa(qi, k, v, pi, kv_positions, causal=causal,
                          scale=scale)

    _, out = jax.lax.scan(body, None, (qc, pc))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * v.shape[-1])


def sdpa_auto(q, k, v, q_positions, kv_positions, *, causal: bool = True,
              scale: float):
    s = q.shape[1]
    if s > Q_CHUNK and s % Q_CHUNK == 0:
        return sdpa_chunked(q, k, v, q_positions, kv_positions, causal=causal,
                            scale=scale)
    return sdpa(q, k, v, q_positions, kv_positions, causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, *, d_model: int, num_heads: int, num_kv_heads: int,
             head_dim: int, qkv_bias: bool = False, qk_norm: bool = False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p: dict[str, Any] = {
        "wq": {"w": lecun_normal(kq, (d_model, num_heads * head_dim))},
        "wk": {"w": lecun_normal(kk, (d_model, num_kv_heads * head_dim))},
        "wv": {"w": lecun_normal(kv, (d_model, num_kv_heads * head_dim))},
        "wo": {"w": lecun_normal(ko, (num_heads * head_dim, d_model))},
    }
    if qkv_bias:
        p["wq"]["b"] = jnp.zeros((num_heads * head_dim,), jnp.float32)
        p["wk"]["b"] = jnp.zeros((num_kv_heads * head_dim,), jnp.float32)
        p["wv"]["b"] = jnp.zeros((num_kv_heads * head_dim,), jnp.float32)
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim)
        p["k_norm"] = rmsnorm_init(head_dim)
    return p


def gqa_init_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                   dtype=jnp.bfloat16):
    shape = (batch, max_len, num_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_apply(p, x, positions, *, num_heads: int, num_kv_heads: int,
              head_dim: int, rope_theta: float = 10000.0, rope: bool = True,
              scale: float | None = None, cache=None, cache_index=None,
              attn_fn=None):
    """x: (B,S,Dm). If ``cache`` given, S is the new-token count (decode) and
    ``cache_index`` the current fill level; returns (out, new_cache).
    ``rope=False`` leaves positions out of q and k (NoPE); ``scale`` is the
    softmax scale, ``head_dim ** -0.5`` when None."""
    b, s, _ = x.shape
    scale = head_dim ** -0.5 if scale is None else scale

    def proj(name, nh):
        y = x @ p[name]["w"]
        if "b" in p[name]:
            y = y + p[name]["b"].astype(y.dtype)
        return y.reshape(b, s, nh, head_dim)

    q = proj("wq", num_heads)
    k = proj("wk", num_kv_heads)
    v = proj("wv", num_kv_heads)
    if "q_norm" in p:
        q = rmsnorm_apply(p["q_norm"], q)
        k = rmsnorm_apply(p["k_norm"], k)
    if rope:
        q = apply_rope(q, positions, theta=rope_theta)
        k = apply_rope(k, positions, theta=rope_theta)

    if cache is None:
        # sequence-parallel -> head-parallel relayout ONCE per layer (the
        # Megatron SP pattern); keeps the chunked-attention scan free of
        # per-chunk collectives.  Only when the head count divides the model
        # axis — otherwise dropping the constraint would REPLICATE the
        # (formerly sequence-sharded) activations, a measured regression on
        # qwen2 (14/12 heads) and musicgen (24 heads).
        if _heads_divide_model(num_heads):
            q = constrain(q, "F", None, "M", None)
            k = constrain(k, "F", None, "M", None)
            v = constrain(v, "F", None, "M", None)
        else:
            q = constrain(q, "F", "M", None, None)
            k = constrain(k, "F", "M", None, None)
            v = constrain(v, "F", "M", None, None)
        kv_positions = positions
        out = (attn_fn or sdpa_auto)(q, k, v, positions, kv_positions,
                                     causal=True, scale=scale)
        out = constrain(out, "F", None, "M")
        return out @ p["wo"]["w"], None

    # decode: write new k/v at cache_index, attend over the whole cache
    new_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1),
    }
    max_len = cache["k"].shape[1]
    kv_positions = jnp.broadcast_to(jnp.arange(max_len)[None, :], (b, max_len))
    # positions beyond the fill level are masked by causality (q position ==
    # cache_index + offset >= any unwritten slot index only if slot <= qpos).
    out = sdpa(q, new_cache["k"].astype(q.dtype), new_cache["v"].astype(q.dtype),
               positions, kv_positions, causal=True, scale=scale)
    return out @ p["wo"]["w"], new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(key, *, d_model: int, num_heads: int, kv_lora_rank: int,
             qk_nope_dim: int = 128, qk_rope_dim: int = 64, v_dim: int = 128):
    kq, kd, ku, ko, kr = jax.random.split(key, 5)
    return {
        "wq": {"w": lecun_normal(kq, (d_model, num_heads * (qk_nope_dim + qk_rope_dim)))},
        "w_dkv": {"w": lecun_normal(kd, (d_model, kv_lora_rank))},
        "w_kr": {"w": lecun_normal(kr, (d_model, qk_rope_dim))},
        "kv_norm": rmsnorm_init(kv_lora_rank),
        "w_ukv": {"w": lecun_normal(ku, (kv_lora_rank, num_heads * (qk_nope_dim + v_dim)))},
        "wo": {"w": lecun_normal(ko, (num_heads * v_dim, d_model))},
    }


def mla_init_cache(batch: int, max_len: int, kv_lora_rank: int,
                   qk_rope_dim: int = 64, dtype=jnp.bfloat16):
    return {"c_kv": jnp.zeros((batch, max_len, kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, qk_rope_dim), dtype)}


def _mla_attend(p, q_nope, q_rope, c_kv, k_rope, q_positions, kv_positions, *,
                num_heads, qk_nope_dim, qk_rope_dim, v_dim):
    b = q_nope.shape[0]
    skv = c_kv.shape[1]
    ukv = (c_kv @ p["w_ukv"]["w"].astype(c_kv.dtype)).reshape(
        b, skv, num_heads, qk_nope_dim + v_dim)
    k_nope, v = ukv[..., :qk_nope_dim], ukv[..., qk_nope_dim:]
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    mask = q_positions[:, None, :, None] >= kv_positions[:, None, None, :]
    logits = jnp.where(mask, logits, BIG_NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out.reshape(b, q_nope.shape[1], num_heads * v_dim)


def mla_apply(p, x, positions, *, num_heads: int, kv_lora_rank: int,
              qk_nope_dim: int = 128, qk_rope_dim: int = 64, v_dim: int = 128,
              rope_theta: float = 10000.0, cache=None, cache_index=None):
    b, s, _ = x.shape
    q = (x @ p["wq"]["w"]).reshape(b, s, num_heads, qk_nope_dim + qk_rope_dim)
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, theta=rope_theta)
    c_kv = rmsnorm_apply(p["kv_norm"], x @ p["w_dkv"]["w"])
    k_rope = apply_rope(x @ p["w_kr"]["w"], positions, theta=rope_theta)

    kw = dict(num_heads=num_heads, qk_nope_dim=qk_nope_dim,
              qk_rope_dim=qk_rope_dim, v_dim=v_dim)
    if cache is None:
        # full-sequence pass: fold MLA into standard attention with
        # head_dim = nope+rope (k_rope broadcast across heads) so the
        # chunked flash-style path applies.
        ukv = (c_kv @ p["w_ukv"]["w"].astype(x.dtype)).reshape(
            b, s, num_heads, qk_nope_dim + v_dim)
        k_nope, v = ukv[..., :qk_nope_dim], ukv[..., qk_nope_dim:]
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                      (b, s, num_heads, qk_rope_dim))], axis=-1)
        # (sdpa contracts the last dim of q/k and uses v's own dim, so the
        # unequal qk/v head dims of MLA are fine.)
        q_eff = constrain(q_eff, "F", None, "M", None)
        k_eff = constrain(k_eff, "F", None, "M", None)
        v = constrain(v, "F", None, "M", None)
        scale = (qk_nope_dim + qk_rope_dim) ** -0.5
        out = sdpa_auto(q_eff, k_eff, v, positions, positions, causal=True,
                        scale=scale)
        out = constrain(out, "F", None, "M")
        return out @ p["wo"]["w"], None

    new_cache = {
        "c_kv": jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_index, axis=1),
        "k_rope": jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_index, axis=1),
    }
    max_len = cache["c_kv"].shape[1]
    kv_positions = jnp.broadcast_to(jnp.arange(max_len)[None, :], (b, max_len))
    # ABSORBED decode (DeepSeek's matrix-absorption trick, §Perf): fold
    # w_ukv into the query and the output so attention runs directly over
    # the compressed latent — per-step cost drops from
    # O(S * kv_lora * H * (nope+v)) to O(S * kv_lora * H), ~d_head x less.
    w_ukv = p["w_ukv"]["w"].astype(x.dtype).reshape(
        -1, num_heads, qk_nope_dim + v_dim)
    w_k, w_v = w_ukv[..., :qk_nope_dim], w_ukv[..., qk_nope_dim:]
    ckv = new_cache["c_kv"].astype(x.dtype)
    kr = new_cache["k_rope"].astype(x.dtype)
    q_abs = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_k)
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    logits = (jnp.einsum("bqhl,bkl->bhqk", q_abs, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    mask = positions[:, None, :, None] >= kv_positions[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, logits, BIG_NEG), axis=-1
                           ).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkl->bqhl", probs, ckv)
    out = jnp.einsum("bqhl,lhd->bqhd", ctx, w_v).reshape(
        b, s, num_heads * v_dim)
    return out @ p["wo"]["w"], new_cache
