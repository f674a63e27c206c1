"""Decode-vs-full-sequence logit consistency for every architecture family.

The strongest end-to-end correctness check in the suite: running the model
token-by-token through `serve_step` (KV caches / WKV states / SSD states /
conv states threaded through the scan) must reproduce the full-sequence
forward pass exactly (up to fp accumulation).  For MoE archs the capacity
factor is raised so routing drops cannot differ between the two paths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm as L

KEY = jax.random.PRNGKey(0)

ARCHS = ["qwen2_0_5b", "qwen3_8b", "gemma_7b", "qwen3_moe_30b_a3b",
         "deepseek_v2_lite_16b", "rwkv6_1_6b", "zamba2_7b", "musicgen_medium",
         "granite_4_0_h_micro"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch).smoke()
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    params = L.init_params(KEY, cfg)
    b, s = 2, 8
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                          cfg.vocab_size)}
    if cfg.frontend == "audio_frames":
        batch["embeds"] = jax.random.normal(KEY, (b, s, cfg.d_model))
    full, _, _ = L.forward(params, cfg, batch)

    serve = jax.jit(L.make_serve_step(cfg))
    state = L.init_decode_state(cfg, b, 16)
    errs = []
    for t in range(s):
        step_batch = {"tokens": batch["tokens"][:, t:t + 1]}
        if cfg.frontend == "audio_frames":
            step_batch["embeds"] = batch["embeds"][:, t:t + 1]
        logits, state = serve(params, step_batch, state,
                              jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full[:, t]))))
    assert max(errs) < 5e-4, f"{arch}: decode diverges from full ({max(errs)})"


def test_granite_prefill_then_decode_matches_full_forward():
    """A hybrid stack's prompt in one prefill call, then one token at a
    time: each attention layer's KV cache and each Mamba-2 layer's SSM and
    conv states are carried side by side.  A chunk of 4 sends the 8-token
    prefill through the chunked scan from a state."""
    cfg = get_config("granite_4_0_h_micro").smoke().replace(ssm_chunk=4)
    params = L.init_params(KEY, cfg)
    b, s, prompt = 2, 12, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    full, _, _ = L.forward(params, cfg, {"tokens": tokens})

    serve = jax.jit(L.make_serve_step(cfg))
    state = L.init_decode_state(cfg, b, 16)
    assert set(state["hybrid"]) == {"attention", "mamba"}
    logits, state = serve(params, {"tokens": tokens[:, :prompt]}, state,
                          jnp.zeros((), jnp.int32))
    errs = [float(jnp.max(jnp.abs(logits - full[:, :prompt])))]
    for t in range(prompt, s):
        logits, state = serve(params, {"tokens": tokens[:, t:t + 1]}, state,
                              jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full[:, t]))))
    assert max(errs) < 5e-4, errs
