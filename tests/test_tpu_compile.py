"""Every Pallas kernel compiles for a TPU v5e at real widths.

The kernel parity tests (``tests/test_kernels.py``) run in interpret mode,
which accepts block shapes and ops the TPU's compiler refuses.  Here each
kernel is lowered through Mosaic and compiled for a described (not
attached) ``v5e:2x2`` topology, one chip of it, and the compiled program
must hold the kernel as a ``tpu_custom_call`` named after it.  Nothing
runs, so this says nothing about numerics or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the suite runs
under several workers.  All these tests live in this one file so that one
worker loads it.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import pop_adam as pa
from repro.kernels import pop_matmul as pm
from repro.kernels import ssd as sd
from repro.kernels import wkv6 as wk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; shapes are (shape, dtype)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, name):
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any(f"%{name}" in line for line in calls), \
        f"no {name} tpu_custom_call in the compiled program"


def _td3_param_count(env_name="hopper2d"):
    """Actor + critic parameters of one TD3 member at the paper's 256-256
    widths."""
    from repro.envs import make
    from repro.rl import td3
    spec = make(env_name).spec
    state = jax.eval_shape(lambda k: td3.init(k, spec.obs_dim, spec.act_dim),
                           jax.random.PRNGKey(0))
    return sum(math.prod(x.shape)
               for x in jax.tree.leaves((state.actor, state.critic)))


@pytest.mark.parametrize("n", [8, 80, 81])
def test_pop_adam_compiles_for_v5e(one_chip, n):
    """The paper's 80 members and a population 8 does not divide fit the
    chip's scoped VMEM as well as 8 do."""
    p, f32 = _td3_param_count(), jnp.float32
    text = _compile(pa.pop_adam, one_chip, *[((n, p), f32)] * 4,
                    ((n,), f32), ((n,), jnp.int32))
    _assert_kernel(text, "pop_adam")


@pytest.mark.parametrize("k,m", [(14, 256), (14, 1), (256, 256), (256, 1)])
def test_pop_matmul_compiles_for_v5e(one_chip, k, m):
    n, b = 8, 256
    assert pm.supports_shapes(b, k, m)
    f32 = jnp.float32
    text = _compile(
        lambda x, w, bias: pm.pop_matmul(x, w, bias, activation="relu"),
        one_chip, ((n, b, k), f32), ((n, k, m), f32), ((n, m), f32))
    _assert_kernel(text, "pop_matmul")


def test_flash_attention_compiles_for_v5e(one_chip):
    cfg = get_config("qwen2_0_5b")
    s, bf16 = 2048, jnp.bfloat16
    text = _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True), one_chip,
        ((1, cfg.num_heads, s, cfg.hd), bf16),
        ((1, cfg.num_kv_heads, s, cfg.hd), bf16),
        ((1, cfg.num_kv_heads, s, cfg.hd), bf16))
    _assert_kernel(text, "flash_attention")


def test_wkv6_compiles_for_v5e(one_chip):
    cfg = get_config("rwkv6_1_6b")
    h, d, s, f32 = cfg.num_heads, cfg.ssm_head_dim, 512, jnp.float32
    text = _compile(
        lambda r, k, v, lw, u, s0: wk.wkv6(r, k, v, lw, u, s0,
                                           chunk=cfg.ssm_chunk),
        one_chip, *[((1, h, s, d), f32)] * 4, ((h, d), f32),
        ((1, h, d, d), f32))
    _assert_kernel(text, "wkv6")


def _ssd_shapes(cfg, s, h):
    """x, dt, a, b, c and the state on the nn layout, float32."""
    p, n, f32 = cfg.ssm_head_dim, cfg.ssm_state, jnp.float32
    return (((1, s, h, p), f32), ((1, s, h), f32), ((h,), f32),
            ((1, s, n), f32), ((1, s, n), f32), ((1, h, p, n), f32))


def test_ssd_compiles_for_v5e(one_chip):
    cfg = get_config("zamba2_7b")
    h = 8                      # heads only repeat the grid; 8 of the 112
    text = _compile(
        lambda x, dt, a, b, c, s0: sd.ssd(x, dt, a, b, c, s0,
                                          chunk=cfg.ssm_chunk),
        one_chip, *_ssd_shapes(cfg, 1024, h))
    _assert_kernel(text, "ssd")


def test_ssd_grad_compiles_for_v5e(one_chip):
    """``jax.grad`` through the kernel pair at granite's widths (P 64, N
    128, chunk 256; 8 of its 64 heads) holds the forward, which writes the
    residual states, and the backward as kernels."""
    cfg = get_config("granite_4_0_h_micro")

    def loss(*args):
        y, sf = sd.ssd(*args, chunk=cfg.ssm_chunk)
        return jnp.sum(y * y) + jnp.sum(sf)

    text = _compile(jax.grad(loss, argnums=range(6)), one_chip,
                    *_ssd_shapes(cfg, 1024, 8))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("ssd_bwd" in line for line in calls) == 1
    assert sum("ssd" in line and "ssd_bwd" not in line
               for line in calls) == 1

