"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and finiteness; plus a decode
step exercising the KV-cache/SSM-state path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LM_SHAPES, TrainConfig, applicable_shapes, get_config
from repro.configs.registry import _ARCHS
from repro.models import lm as L

KEY = jax.random.PRNGKey(0)


def _smoke_batch(cfg, b=2, s=32):
    batch = {"tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    if cfg.frontend == "audio_frames":
        batch["embeds"] = jax.random.normal(KEY, (b, s, cfg.d_model))
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jax.random.normal(
            KEY, (b, cfg.num_frontend_positions, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", _ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).smoke()
    params = L.init_params(KEY, cfg)
    batch = _smoke_batch(cfg)
    logits, _, aux = L.forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    opt_init, train_step = L.make_train_step(
        cfg, TrainConfig(total_steps=10, warmup_steps=0))
    opt = opt_init(params)
    p2, opt2, metrics = jax.jit(train_step)(params, opt, batch,
                                            jnp.zeros((), jnp.int32))
    assert np.isfinite(float(metrics["loss"]))
    # parameters actually moved
    moved = any(float(jnp.max(jnp.abs(a - b))) > 0
                for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)))
    assert moved


@pytest.mark.parametrize("arch", _ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch).smoke()
    params = L.init_params(KEY, cfg)
    serve = jax.jit(L.make_serve_step(cfg))
    state = L.init_decode_state(cfg, 2, 16)
    batch = {"tokens": jax.random.randint(KEY, (2, 1), 0, cfg.vocab_size)}
    if cfg.frontend == "audio_frames":
        batch["embeds"] = jax.random.normal(KEY, (2, 1, cfg.d_model))
    logits, state = serve(params, batch, state, jnp.zeros((), jnp.int32))
    logits, state = serve(params, batch, state, jnp.ones((), jnp.int32))
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


def test_applicable_shapes_policy():
    """long_500k runs only for sub-quadratic archs (DESIGN.md)."""
    long_ok = {a for a in _ARCHS
               if "long_500k" in applicable_shapes(get_config(a))}
    assert long_ok == {"rwkv6_1_6b", "zamba2_7b", "rwkv6_test"}
    for a in _ARCHS:
        shapes = applicable_shapes(get_config(a))
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (the 10-arch table)."""
    spec = {
        "musicgen_medium": (48, 1536, 24, 24, 6144, 2048),
        "qwen3_moe_30b_a3b": (48, 2048, 32, 4, 768, 151936),
        "deepseek_v2_lite_16b": (27, 2048, 16, 16, 1408, 102400),
        "pixtral_12b": (40, 5120, 32, 8, 14336, 131072),
        "rwkv6_1_6b": (24, 2048, 32, 32, 7168, 65536),
        "zamba2_7b": (81, 3584, 32, 32, 14336, 32000),
        "qwen2_1_5b": (28, 1536, 12, 2, 8960, 151936),
        "qwen3_8b": (36, 4096, 32, 8, 12288, 151936),
        "gemma_7b": (28, 3072, 16, 16, 24576, 256000),
        "qwen2_0_5b": (24, 896, 14, 2, 4864, 151936),
        "granite_4_0_h_micro": (40, 2048, 32, 8, 8192, 100352),
    }
    for arch, (nl, dm, nh, kv, ff, vs) in spec.items():
        c = get_config(arch)
        assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
                c.d_ff, c.vocab_size) == (nl, dm, nh, kv, ff, vs), arch
    assert get_config("qwen3_moe_30b_a3b").moe.num_experts == 128
    assert get_config("qwen3_moe_30b_a3b").moe.top_k == 8
    assert get_config("deepseek_v2_lite_16b").moe.top_k == 6
    assert get_config("deepseek_v2_lite_16b").mla.kv_lora_rank == 512
    assert get_config("zamba2_7b").ssm_state == 64
    assert get_config("gemma_7b").hd == 256
    granite = get_config("granite_4_0_h_micro")
    assert granite.ssm_state == 128
    assert granite.layer_types == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4


def test_logits_chunk_loss_equivalence():
    cfg = get_config("qwen2_0_5b").smoke()
    params = L.init_params(KEY, cfg)
    batch = _smoke_batch(cfg)
    l1, _ = L.lm_loss(params, cfg, batch)
    l2, _ = L.lm_loss(params, cfg.replace(logits_chunk=8), batch)
    assert abs(float(l1) - float(l2)) < 1e-5
