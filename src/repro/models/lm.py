"""Unified decoder LM covering all assigned architectures.

One config-driven model family:
  * dense / MoE / MLA attention transformers (qwen2/3, gemma, pixtral,
    musicgen, qwen3-moe, deepseek-v2-lite)
  * RWKV6 (attention-free)
  * Mamba2 (+ Zamba2 shared-attention hybrid)
  * hybrid stacks whose layers each pick a mixer (Mamba2 or attention) and
    follow it with an MLP (granite-4.0-h), scanned one period at a time

Structure is organised as *segments* of homogeneous blocks; each segment is a
``jax.lax.scan`` over stacked layer parameters (keeps the HLO small enough
that the 512-device dry-run compiles for 48-81 layer models).  Decode state
(KV caches / SSM states) is threaded through the same scans as stacked xs/ys.

Public API:
    init_params(key, cfg)
    forward(params, cfg, batch, state=None, cache_index=None)
    make_train_step(cfg, tcfg) / make_serve_step(cfg)
    init_decode_state(cfg, batch, max_len)
    input_specs(cfg, shape)  -> ShapeDtypeStruct stand-ins (no allocation)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig, ShapeSpec, TrainConfig
from repro.kernels import ops as kernel_ops
from repro.models.sharding import constrain, constrain_tree
from repro.nn.attention import (gqa_apply, gqa_init, mla_apply, mla_init)
from repro.nn.basic import (cast, embedding_init, glu_mlp_apply, glu_mlp_init,
                            layernorm_apply, layernorm_init, lecun_normal,
                            rmsnorm_apply, rmsnorm_init)
from repro.nn.mamba2 import mamba2_block_apply, mamba2_block_init
from repro.nn.moe import moe_apply, moe_init
from repro.nn.rwkv6 import (channel_mix_apply, rwkv6_block_init,
                            time_mix_apply)
from repro.optim import (adam, apply_updates, dynamic_warmup_cosine,
                         population_adam, warmup_cosine)


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    name: str
    kind: str            # attn | rwkv | mamba | hybrid
    count: int           # scan length
    inner: int = 1       # mamba layers per scanned super-block
    moe: bool = False
    shared_attn: bool = False
    pattern: tuple = ()  # hybrid: the mixers of one scanned period, in order


def _period(types: tuple) -> tuple:
    """The shortest prefix of ``types`` that repeats to make all of it."""
    n = len(types)
    for p in range(1, n + 1):
        if n % p == 0 and types == types[:p] * (n // p):
            return types[:p]
    raise ValueError("empty layer_types")


def layout(cfg: LMConfig) -> list[Segment]:
    if cfg.block_type == "attention":
        nd = cfg.num_layers if cfg.moe is None else cfg.moe.first_dense_layers
        nm = 0 if cfg.moe is None else cfg.num_layers - nd
        segs = []
        if nd:
            segs.append(Segment("dense", "attn", nd))
        if nm:
            segs.append(Segment("moe", "attn", nm, moe=True))
        return segs
    if cfg.block_type == "rwkv6":
        return [Segment("rwkv", "rwkv", cfg.num_layers)]
    if cfg.block_type == "mamba2":
        if cfg.shared_attn_every:
            inner = cfg.shared_attn_every
            n_super, rem = divmod(cfg.num_layers, inner)
            segs = [Segment("mamba_main", "mamba", n_super, inner=inner,
                            shared_attn=True)]
            if rem:
                segs.append(Segment("mamba_tail", "mamba", 1, inner=rem,
                                    shared_attn=True))
            return segs
        return [Segment("mamba", "mamba", cfg.num_layers)]
    if cfg.block_type == "hybrid":
        types = tuple(cfg.layer_types)
        if len(types) != cfg.num_layers or \
                not set(types) <= {"mamba", "attention"}:
            raise ValueError(f"layer_types {types} for {cfg.num_layers} "
                             "layers of mamba | attention")
        period = _period(types)
        return [Segment("hybrid", "hybrid", cfg.num_layers // len(period),
                        pattern=period)]
    raise ValueError(cfg.block_type)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _attn_block_init(key, cfg: LMConfig, moe_layer: bool):
    k1, k2 = jax.random.split(key)
    p: dict[str, Any] = {"attn_norm": rmsnorm_init(cfg.d_model),
                         "mlp_norm": rmsnorm_init(cfg.d_model)}
    if cfg.mla is not None:
        p["attn"] = mla_init(k1, d_model=cfg.d_model, num_heads=cfg.num_heads,
                             kv_lora_rank=cfg.mla.kv_lora_rank,
                             qk_nope_dim=cfg.mla.qk_nope_dim,
                             qk_rope_dim=cfg.mla.qk_rope_dim,
                             v_dim=cfg.mla.v_dim)
    else:
        p["attn"] = gqa_init(k1, d_model=cfg.d_model, num_heads=cfg.num_heads,
                             num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
                             qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    if moe_layer:
        m = cfg.moe
        p["mlp"] = moe_init(k2, d_model=cfg.d_model, d_expert=m.d_expert,
                            num_experts=m.num_experts, num_shared=m.num_shared)
    else:
        p["mlp"] = glu_mlp_init(k2, cfg.d_model, cfg.d_ff)
    return p


def _attn_block_apply(p, cfg: LMConfig, h, positions, cache, cache_index,
                      moe_layer: bool, use_kernels):
    p = constrain_tree(p)  # pins param+cotangent shardings inside the scan
    y = rmsnorm_apply(p["attn_norm"], h, eps=cfg.norm_eps)
    if cfg.mla is not None:
        m = cfg.mla
        y, new_cache = mla_apply(
            p["attn"], y, positions, num_heads=cfg.num_heads,
            kv_lora_rank=m.kv_lora_rank, qk_nope_dim=m.qk_nope_dim,
            qk_rope_dim=m.qk_rope_dim, v_dim=m.v_dim,
            rope_theta=cfg.rope_theta, cache=cache, cache_index=cache_index)
    else:
        y, new_cache = gqa_apply(
            p["attn"], y, positions, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
            rope_theta=cfg.rope_theta, cache=cache, cache_index=cache_index,
            attn_fn=kernel_ops.attention_fn(use_kernels.attention))
    h = constrain(h + y, "F", "M", None)
    y = rmsnorm_apply(p["mlp_norm"], h, eps=cfg.norm_eps)
    if moe_layer:
        m = cfg.moe
        y, aux = moe_apply(p["mlp"], y, num_experts=m.num_experts, top_k=m.top_k,
                           capacity_factor=m.capacity_factor,
                           group_size=m.group_size, activation=cfg.activation)
    else:
        y, aux = glu_mlp_apply(p["mlp"], y, activation=cfg.activation), \
            jnp.zeros((), jnp.float32)
    h = constrain(h + y, "F", "M", None)
    return h, new_cache, aux


def _rwkv_block_init(key, cfg: LMConfig):
    p = rwkv6_block_init(key, d_model=cfg.d_model, d_ff=cfg.d_ff,
                         head_dim=cfg.ssm_head_dim)
    p["ln1"] = layernorm_init(cfg.d_model)
    p["ln2"] = layernorm_init(cfg.d_model)
    return p


def _rwkv_block_apply(p, cfg: LMConfig, h, state, use_kernels):
    """state: {"wkv","tm_x","cm_x"} (decode) or None (fresh zeros)."""
    p = constrain_tree(p)
    b = h.shape[0]
    nh = cfg.d_model // cfg.ssm_head_dim
    if state is None:
        state = {
            "wkv": jnp.zeros((b, nh, cfg.ssm_head_dim, cfg.ssm_head_dim),
                             jnp.float32),
            "tm_x": jnp.zeros((b, 1, cfg.d_model), h.dtype),
            "cm_x": jnp.zeros((b, 1, cfg.d_model), h.dtype),
        }
    x = layernorm_apply(p["ln1"], h)
    y, wkv, tm_x = time_mix_apply(p["time_mix"], x, state["tm_x"].astype(h.dtype),
                                  state["wkv"], head_dim=cfg.ssm_head_dim,
                                  use_chunked=cfg.use_chunked,
                                  chunk=min(cfg.ssm_chunk, 64),
                                  compute_dtype=jnp.dtype(cfg.ssm_compute_dtype),
                                  use_kernels=use_kernels.wkv6)
    h = constrain(h + y, "F", "M", None)
    x = layernorm_apply(p["ln2"], h)
    y, cm_x = channel_mix_apply(p["channel_mix"], x, state["cm_x"].astype(h.dtype))
    h = constrain(h + y, "F", "M", None)
    new_state = {"wkv": wkv, "tm_x": tm_x.astype(state["tm_x"].dtype),
                 "cm_x": cm_x.astype(state["cm_x"].dtype)}
    return h, new_state


def _mamba_layer_init(key, cfg: LMConfig):
    return {"norm": rmsnorm_init(cfg.d_model),
            "mamba": mamba2_block_init(key, d_model=cfg.d_model,
                                       d_state=cfg.ssm_state,
                                       head_dim=cfg.ssm_head_dim)}


def _mamba_mixer(p, cfg: LMConfig, x, state, use_kernels):
    """The Mamba-2 mixer on the normed input ``x``; a zero state (a fresh
    sequence) when ``state`` is None."""
    if state is None:
        b = x.shape[0]
        d_inner = 2 * cfg.d_model
        nh = d_inner // cfg.ssm_head_dim
        state = {"ssm": jnp.zeros((b, nh, cfg.ssm_head_dim, cfg.ssm_state),
                                  jnp.float32),
                 "conv": jnp.zeros((b, 3, d_inner + 2 * cfg.ssm_state), x.dtype)}
    with jax.named_scope("mamba"):
        return mamba2_block_apply(
            p, x, state, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            use_chunked=cfg.use_chunked, chunk=cfg.ssm_chunk,
            compute_dtype=jnp.dtype(cfg.ssm_compute_dtype),
            norm_eps=cfg.norm_eps, use_kernels=use_kernels.ssd)


def _mamba_layer_apply(p, cfg: LMConfig, h, state, use_kernels):
    p = constrain_tree(p)
    y, new_state = _mamba_mixer(
        p["mamba"], cfg, rmsnorm_apply(p["norm"], h, eps=cfg.norm_eps),
        state, use_kernels)
    return constrain(h + y, "F", "M", None), new_state


def _hybrid_layer_init(key, cfg: LMConfig, mixer: str):
    k1, k2 = jax.random.split(key)
    if mixer == "attention":
        mix = gqa_init(k1, d_model=cfg.d_model, num_heads=cfg.num_heads,
                       num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
                       qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    else:
        mix = mamba2_block_init(k1, d_model=cfg.d_model,
                                d_state=cfg.ssm_state,
                                head_dim=cfg.ssm_head_dim)
    return {"norm": rmsnorm_init(cfg.d_model), "mixer": mix,
            "mlp_norm": rmsnorm_init(cfg.d_model),
            "mlp": glu_mlp_init(k2, cfg.d_model, cfg.d_ff)}


def _hybrid_period_init(key, cfg: LMConfig, pattern: tuple):
    """One period's layers, stacked per mixer: ``{mixer: (n, ...)}``."""
    return {m: _stacked_init(jax.random.fold_in(key, i), pattern.count(m),
                             partial(_hybrid_layer_init, cfg=cfg, mixer=m))
            for i, m in enumerate(sorted(set(pattern)))}


def _scaled(y, multiplier: float):
    return y if multiplier == 1.0 else y * multiplier


def _hybrid_layer_apply(p, cfg: LMConfig, mixer: str, h, positions, state,
                        cache_index, use_kernels):
    """One hybrid layer: ``h += r * mixer(rms(h))``, then ``h += r *
    mlp(rms(h))``, ``r`` the residual multiplier.  ``state`` is the
    mixer's decode state (a KV cache or the SSM and conv states)."""
    p = constrain_tree(p)
    y = rmsnorm_apply(p["norm"], h, eps=cfg.norm_eps)
    if mixer == "attention":
        with jax.named_scope("attention"):
            y, new_state = gqa_apply(
                p["mixer"], y, positions, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta,
                rope=cfg.position_embedding == "rope",
                scale=cfg.attention_multiplier, cache=state,
                cache_index=cache_index,
                attn_fn=kernel_ops.attention_fn(use_kernels.attention))
    else:
        y, new_state = _mamba_mixer(p["mixer"], cfg, y, state, use_kernels)
    h = constrain(h + _scaled(y, cfg.residual_multiplier), "F", "M", None)
    y = glu_mlp_apply(p["mlp"], rmsnorm_apply(p["mlp_norm"], h, eps=cfg.norm_eps),
                      activation=cfg.activation)
    h = constrain(h + _scaled(y, cfg.residual_multiplier), "F", "M", None)
    return h, new_state


def _hybrid_period_apply(seg: Segment, p, state, cfg: LMConfig, h, positions,
                         cache_index, remat: bool, use_kernels):
    """The layers of one period in ``seg.pattern`` order, each taking the
    next layer of its mixer's stack.  With ``remat`` each layer is
    rematerialised on its own, so the backward pass holds the activations
    of one layer at a time, not of the whole period."""
    taken = {m: 0 for m in p}
    new = {m: [] for m in p}
    for mixer in seg.pattern:
        i = taken[mixer]
        taken[mixer] += 1

        def layer(p_m, h, st_m, i=i, mixer=mixer):
            take = lambda t: jax.tree.map(lambda a: a[i], t)
            return _hybrid_layer_apply(
                take(p_m), cfg, mixer, h, positions,
                None if st_m is None else take(st_m), cache_index,
                use_kernels)

        if remat:
            layer = jax.checkpoint(layer)
        h, st = layer(p[mixer], h, None if state is None else state[mixer])
        new[mixer].append(st)
    if state is None:
        return h, None
    return h, {m: jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
               for m, sts in new.items()}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stacked_init(key, n, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key, cfg: LMConfig):
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {"segments": {}}
    if cfg.frontend != "audio_frames":
        params["embed"] = embedding_init(keys[0], cfg.vocab_size, cfg.d_model)
    for i, seg in enumerate(layout(cfg)):
        kseg = jax.random.fold_in(keys[1], i)
        if seg.kind == "attn":
            fn = partial(_attn_block_init, cfg=cfg, moe_layer=seg.moe)
            params["segments"][seg.name] = _stacked_init(kseg, seg.count, fn)
        elif seg.kind == "rwkv":
            fn = partial(_rwkv_block_init, cfg=cfg)
            params["segments"][seg.name] = _stacked_init(kseg, seg.count, fn)
        elif seg.kind == "hybrid":
            fn = partial(_hybrid_period_init, cfg=cfg, pattern=seg.pattern)
            params["segments"][seg.name] = _stacked_init(kseg, seg.count, fn)
        else:  # mamba / zamba super-blocks
            fn = partial(_mamba_layer_init, cfg=cfg)
            if seg.inner > 1 or seg.shared_attn:
                inner_fn = lambda k: _stacked_init(k, seg.inner, fn)
                params["segments"][seg.name] = _stacked_init(kseg, seg.count,
                                                             inner_fn)
            else:
                params["segments"][seg.name] = _stacked_init(kseg, seg.count, fn)
    if cfg.shared_attn_every:
        params["shared_attn"] = _attn_block_init(keys[2], cfg, moe_layer=False)
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": lecun_normal(keys[3],
                                               (cfg.d_model, cfg.vocab_size))}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _segment_forward(seg: Segment, seg_params, shared_p, cfg: LMConfig, h,
                     positions, seg_state, cache_index, train: bool,
                     use_kernels):
    collect_state = seg_state is not None

    def body(h, xs):
        layer_p, layer_st = xs
        aux = jnp.zeros((), jnp.float32)
        if seg.kind == "attn":
            cache = layer_st["kv"] if collect_state else None
            h, new_cache, aux = _attn_block_apply(
                layer_p, cfg, h, positions, cache, cache_index, seg.moe,
                use_kernels)
            new_st = {"kv": new_cache} if collect_state else None
        elif seg.kind == "rwkv":
            h, new_st = _rwkv_block_apply(layer_p, cfg, h,
                                          layer_st if collect_state else None,
                                          use_kernels)
            new_st = new_st if collect_state else None
        elif seg.kind == "hybrid":
            h, new_st = _hybrid_period_apply(
                seg, layer_p, layer_st if collect_state else None, cfg, h,
                positions, cache_index, cfg.remat and train, use_kernels)
        else:  # mamba (possibly zamba super-block with shared attention)
            if seg.shared_attn:
                cache = layer_st["attn"]["kv"] if collect_state else None
                h, new_cache, _ = _attn_block_apply(
                    shared_p, cfg, h, positions, cache, cache_index, False,
                    use_kernels)
                new_mamba = []
                for i in range(seg.inner):
                    pi = jax.tree.map(lambda a: a[i], layer_p)
                    sti = (jax.tree.map(lambda a: a[i], layer_st["mamba"])
                           if collect_state else None)
                    h, st_i = _mamba_layer_apply(pi, cfg, h, sti, use_kernels)
                    new_mamba.append(st_i)
                if collect_state:
                    new_st = {"attn": {"kv": new_cache},
                              "mamba": jax.tree.map(
                                  lambda *xs: jnp.stack(xs), *new_mamba)}
                else:
                    new_st = None
            else:
                h, new_st = _mamba_layer_apply(layer_p, cfg, h,
                                               layer_st if collect_state else None,
                                               use_kernels)
                new_st = new_st if collect_state else None
        return h, (new_st, aux)

    if cfg.remat and train and seg.kind != "hybrid":  # hybrid: per layer
        body = jax.checkpoint(body)
    # the scope wraps the scan call, not only its body, so that the loop
    # instruction itself carries it
    with jax.named_scope("layers"):
        h, (new_states, auxs) = jax.lax.scan(body, h,
                                             (seg_params, seg_state))
    return h, new_states, jnp.sum(auxs)


def forward(params, cfg: LMConfig, batch, state=None, cache_index=None,
            train: bool = False, return_hidden: bool = False):
    """batch: {"tokens": (B,S) int32, ["embeds"], ["patch_embeds"]}.

    Returns (logits_or_hidden, new_state, aux_loss)."""
    dtype = jnp.dtype(cfg.dtype)
    cparams = cast(params, dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape

    if cfg.frontend == "audio_frames":
        h = batch["embeds"].astype(dtype)
    else:
        with jax.named_scope("embed"):
            h = cparams["embed"]["embedding"][tokens]
        if cfg.frontend == "vision_patches" and "patch_embeds" in batch:
            npatch = batch["patch_embeds"].shape[1]
            if cache_index is None:  # full-sequence pass: splice patch prefix
                h = jnp.concatenate(
                    [batch["patch_embeds"].astype(dtype), h[:, npatch:]], axis=1)
    if cfg.family == "dense" and cfg.name.startswith("gemma"):
        h = h * jnp.asarray(cfg.d_model ** 0.5, dtype)
    h = _scaled(h, cfg.embedding_multiplier)

    if cache_index is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    else:
        positions = cache_index + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s))

    h = constrain(h, "F", "M", None)
    # kernels/ops dispatch, per kernel: "auto" (None) means kernels only on
    # TPU; only the SSD kernel has a custom VJP, so a differentiated
    # (training) forward keeps flash attention and wkv6 on the jnp path.
    uk = kernel_ops.resolve_kernels(cfg.use_kernels, train=train,
                                    use_flash=cfg.use_flash)
    aux_total = jnp.zeros((), jnp.float32)
    new_state = {} if state is not None else None
    for seg in layout(cfg):
        seg_state = state[seg.name] if state is not None else None
        shared_p = cparams.get("shared_attn")
        h, seg_new, aux = _segment_forward(
            seg, cparams["segments"][seg.name], shared_p, cfg, h, positions,
            seg_state, cache_index, train, uk)
        if state is not None:
            new_state[seg.name] = seg_new
        aux_total = aux_total + aux

    with jax.named_scope("head"):
        h = rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps)
        if return_hidden:
            return h, new_state, aux_total
        logits = _logits(h, _head_weight(cparams, cfg), cfg)
    return logits, new_state, aux_total


def _logits(h, w, cfg: LMConfig):
    """The head: the final hidden state against ``w``, over
    ``logits_scaling``."""
    logits = h @ w
    return logits if cfg.logits_scaling == 1.0 else \
        logits / cfg.logits_scaling


def _head_weight(cparams, cfg: LMConfig):
    if cfg.tie_embeddings:
        # vocab-shard the tied head even when the embedding table itself is
        # replicated (population mode): keeps the logits vocab-parallel.
        return constrain(cparams["embed"]["embedding"].T, None, "M")
    return cparams["lm_head"]["w"]


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------


def _token_ce(logits, labels, mask):
    logits = constrain(logits.astype(jnp.float32), "F", None, "M")
    logz = jax.nn.logsumexp(logits, axis=-1)
    # gold logit via a fused masked reduction instead of take_along_axis:
    # the gather on the vocab-sharded axis forced XLA to all-gather the
    # full fp32 logits; the where+sum keeps everything vocab-local and
    # all-reduces only the (B,S) partials (§Perf CE iteration).
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    ce = (logz - gold) * mask
    return jnp.sum(ce), jnp.sum(mask)


def lm_loss(params, cfg: LMConfig, batch, train: bool = True):
    hidden, _, aux = forward(params, cfg, batch, train=train,
                             return_hidden=True)
    tokens = batch["tokens"]
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    if cfg.frontend == "vision_patches" and cfg.num_frontend_positions:
        mask = mask.at[:, :cfg.num_frontend_positions].set(0.0)
    with jax.named_scope("head"):
        w = _head_weight(cast(params, jnp.dtype(cfg.dtype)), cfg)
        if cfg.logits_chunk and hidden.shape[1] % cfg.logits_chunk == 0:
            nc = hidden.shape[1] // cfg.logits_chunk
            def body(carry, xs):
                h_c, l_c, m_c = xs
                ce, n = _token_ce(_logits(h_c, w, cfg), l_c, m_c)
                return (carry[0] + ce, carry[1] + n), None
            reshape = lambda x: jnp.moveaxis(x.reshape(
                x.shape[0], nc, cfg.logits_chunk, *x.shape[2:]), 1, 0)
            (ce, n), _ = jax.lax.scan(
                body, (jnp.zeros(()), jnp.zeros(())),
                (reshape(hidden), reshape(labels), reshape(mask)))
        else:
            ce, n = _token_ce(_logits(hidden, w, cfg), labels, mask)
    loss = ce / jnp.maximum(n, 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux / max(
            cfg.num_layers - cfg.moe.first_dense_layers, 1)
    return loss, {"ce": ce / jnp.maximum(n, 1.0), "aux": aux}


def _make_grads_fn(cfg: LMConfig, tcfg: TrainConfig):
    """Per-member gradient pass shared by the stock train step (scalar, run
    under vmap by the vectorized backend) and the fused population update
    (vmapped here) — ONE definition so both paths trace the same HLO."""

    def grads_of(params, batch):
        if tcfg.grad_accum > 1:
            # microbatching: split the batch over the leading axis and
            # accumulate grads in fp32 via a scan (memory ~1/grad_accum)
            k = tcfg.grad_accum
            micro = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)

            def body(acc, mb):
                (l, m), g = jax.value_and_grad(
                    lambda p: lm_loss(p, cfg, mb), has_aux=True)(params)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32) / k, acc, g)
                return acc, (l, m)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, ms) = jax.lax.scan(body, zeros, micro)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, ms)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: lm_loss(p, cfg, batch), has_aux=True)(params)
        return grads, loss, metrics

    return grads_of


def _make_lr_fn(tcfg: TrainConfig):
    """``lr_at(step, lr_scale, warmup_frac)``: the static warmup-cosine
    schedule when ``warmup_frac`` is None (legacy numerics), the dynamic
    schedule when it is a traced PBT hyper.  Elementwise, so evaluating it
    on ``(N,)`` vectors matches the scalar form under vmap bitwise."""
    static = warmup_cosine(tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
    dynamic = dynamic_warmup_cosine(tcfg.lr, tcfg.total_steps)

    def lr_at(step, lr_scale=None, warmup_frac=None):
        lr = static(step) if warmup_frac is None else dynamic(step, warmup_frac)
        if lr_scale is not None:
            lr = lr * lr_scale
        return lr

    return lr_at


def make_train_step(cfg: LMConfig, tcfg: TrainConfig):
    opt_init, opt_update = adam(tcfg.lr, weight_decay=tcfg.weight_decay,
                                max_grad_norm=tcfg.max_grad_norm)
    grads_of = _make_grads_fn(cfg, tcfg)
    lr_at = _make_lr_fn(tcfg)

    def train_step(params, opt_state, batch, step, lr_scale=None,
                   weight_decay=None, warmup_frac=None):
        grads, loss, metrics = grads_of(params, batch)
        with jax.named_scope("optimizer"):
            lr = lr_at(step, lr_scale, warmup_frac)
            updates, opt_state = opt_update(grads, opt_state, params,
                                            lr_override=lr,
                                            wd_override=weight_decay)
            params = apply_updates(params, updates)
        metrics = dict(metrics, loss=loss, step=step)
        return params, opt_state, metrics

    return opt_init, train_step


def make_population_update(cfg: LMConfig, tcfg: TrainConfig, *, fused=None):
    """Population-level LM update with the optimizer hoisted into
    :func:`repro.optim.population_adam` (PR 8's fused_adam hoist, LM
    edition): per-member gradients under vmap, ONE flattened ``(N, P)``
    Adam application for the whole population.  Signature matches the
    backend registry's fused protocol::

        update(pop_state, batch, hypers) -> (pop_state, metrics)

    ``hypers`` may carry per-member ``lr_scale`` / ``weight_decay`` /
    ``warmup_frac`` vectors; absent keys fall back to the static
    ``TrainConfig`` values — in both cases the result is bitwise-equal to
    the stock ``train_step`` under vmap (``tests/test_lm_population.py``
    pins this on the tiny config)."""
    _, pop_apply = population_adam(
        tcfg.lr, weight_decay=tcfg.weight_decay,
        max_grad_norm=tcfg.max_grad_norm, fused=fused)
    grads_of = _make_grads_fn(cfg, tcfg)
    lr_at = _make_lr_fn(tcfg)

    def pop_update(state, batch, hypers=None):
        from repro.pop.agent import LMState  # lazy: pop.agent imports lm
        h = hypers if hypers else {}
        grads, loss, metrics = jax.vmap(grads_of)(state.params, batch)
        with jax.named_scope("optimizer"):
            lr = lr_at(state.step, h.get("lr_scale"), h.get("warmup_frac"))
            params, opt_state = pop_apply(
                state.params, grads, state.opt_state, lr_override=lr,
                wd_override=h.get("weight_decay"))
        metrics = dict(metrics, loss=loss, step=state.step)
        return LMState(params=params, opt_state=opt_state,
                       step=state.step + 1), metrics

    return pop_update


def make_serve_step(cfg: LMConfig):
    def serve_step(params, batch, state, cache_index):
        logits, new_state, _ = forward(params, cfg, batch, state=state,
                                       cache_index=cache_index)
        return logits, new_state
    return serve_step


# ---------------------------------------------------------------------------
# decode state + input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def _is_shape(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _seg_state_shape(seg: Segment, cfg: LMConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.mla is not None and seg.kind == "attn":
        attn = {"c_kv": ((batch, max_len, cfg.mla.kv_lora_rank), dtype),
                "k_rope": ((batch, max_len, cfg.mla.qk_rope_dim), dtype)}
    else:
        attn = {"k": ((batch, max_len, cfg.num_kv_heads, cfg.hd), dtype),
                "v": ((batch, max_len, cfg.num_kv_heads, cfg.hd), dtype)}
    if seg.kind == "attn":
        return {"kv": attn}
    if seg.kind == "rwkv":
        nh = cfg.d_model // cfg.ssm_head_dim
        return {"wkv": ((batch, nh, cfg.ssm_head_dim, cfg.ssm_head_dim),
                        jnp.float32),
                "tm_x": ((batch, 1, cfg.d_model), dtype),
                "cm_x": ((batch, 1, cfg.d_model), dtype)}
    d_inner = 2 * cfg.d_model
    nh = d_inner // cfg.ssm_head_dim
    mamba = {"ssm": ((batch, nh, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
             "conv": ((batch, 3, d_inner + 2 * cfg.ssm_state), dtype)}
    stack = lambda tree, n: jax.tree.map(lambda t: ((n,) + t[0], t[1]), tree,
                                         is_leaf=_is_shape)
    if seg.kind == "hybrid":
        per = {"mamba": mamba, "attention": attn}
        return {m: stack(per[m], seg.pattern.count(m))
                for m in sorted(set(seg.pattern))}
    if seg.shared_attn:
        mamba = {"mamba": stack(mamba, seg.inner), "attn": {"kv": attn}}
    return mamba


def _materialize(tree, make):
    return jax.tree.map(lambda t: make(t[0], t[1]), tree, is_leaf=_is_shape)


def decode_state_shapes(cfg: LMConfig, batch: int, max_len: int):
    out = {}
    for seg in layout(cfg):
        shapes = _seg_state_shape(seg, cfg, batch, max_len)
        out[seg.name] = _materialize(
            shapes, lambda s, d: ((seg.count,) + s, d))
    return out


def init_decode_state(cfg: LMConfig, batch: int, max_len: int):
    shapes = decode_state_shapes(cfg, batch, max_len)
    return jax.tree.map(lambda t: jnp.zeros(t[0], t[1]), shapes,
                        is_leaf=_is_shape)


def decode_state_specs(cfg: LMConfig, batch: int, max_len: int):
    shapes = decode_state_shapes(cfg, batch, max_len)
    return jax.tree.map(lambda t: jax.ShapeDtypeStruct(t[0], t[1]), shapes,
                        is_leaf=_is_shape)


def input_specs(cfg: LMConfig, shape: ShapeSpec):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b = shape.global_batch
    if shape.kind in ("train", "prefill"):
        s = shape.seq_len
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    else:  # decode: one new token against a seq_len cache
        batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["embeds"] = jax.ShapeDtypeStruct(
            (b, batch["tokens"].shape[1], cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.frontend == "vision_patches" and shape.kind != "decode":
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_frontend_positions, cfg.d_model), jnp.dtype(cfg.dtype))
    return batch
