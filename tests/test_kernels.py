"""Kernel parity wall: property-based sweeps vs the pure-jnp oracles.

Every Pallas kernel runs in interpret mode against its ``repro.kernels.ref``
oracle over two layers of cases:

  * deterministic seeded sweeps — a seeded RNG draws shapes/dtypes at
    collection time, so the same cases run everywhere, every time (pop=1,
    odd dims, zero grads, lr=0 and other edges are pinned explicitly);
  * hypothesis variants — the same properties under randomized search,
    gated on ``import hypothesis`` (tier-1 CI installs it; the suite stays
    green without it).

The population-batched network applies (``repro.rl.networks.pop_*``) are
checked here too: the jnp fallback must be BITWISE equal to ``vmap`` of the
per-member apply (that equality is what makes ``fused_linear`` a pure
routing decision), and the kernel path — forward and ``custom_vjp``
backward — must match to interpret-mode tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.pop_adam import VMEM_BUDGET, pop_adam
from repro.kernels.pop_adam import tiles as pop_adam_tiles
from repro.kernels.pop_matmul import supports_shapes
from repro.models import lm as L
from repro.nn.basic import mlp_init, mlp_apply
from repro.rl import networks as nets

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - tier-1 CI installs it
    HAVE_HYPOTHESIS = False

    def given(**kw):         # decoration-time no-ops: the tests under them
        return lambda f: f   # are skipif'd, but must still collect

    settings = given

    class _NullStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NullStrategies()

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")

KEY = jax.random.PRNGKey(0)

TOL = {jnp.float32: dict(atol=2e-4, rtol=2e-4),
       jnp.bfloat16: dict(atol=0.15, rtol=0.1)}

# one seeded generator, drawn at collection: the deterministic layer of the
# property suite (same cases on every machine, no hypothesis needed)
_RNG = np.random.default_rng(20260808)


def _draw_matmul_cases():
    # pinned edges: pop=1, singleton dims, odd dims, block-aligned 128s
    cases = [(1, 1, 1, 1, "none"), (1, 8, 3, 5, "tanh"),
             (3, 7, 5, 9, "relu"), (2, 128, 128, 128, "none"),
             (1, 256, 64, 128, "relu"), (5, 128, 128, 256, "tanh")]
    for _ in range(8):
        cases.append((int(_RNG.integers(1, 7)), int(_RNG.integers(1, 97)),
                      int(_RNG.integers(1, 97)), int(_RNG.integers(1, 97)),
                      str(_RNG.choice(["none", "relu", "tanh"]))))
    return cases


def _matmul_parity(n, b, k, m, act, dtype, *, bias=True):
    ks = jax.random.split(jax.random.fold_in(KEY, n * b * k * m), 3)
    x = jax.random.normal(ks[0], (n, b, k), dtype)
    w = jax.random.normal(ks[1], (n, k, m), dtype) / np.sqrt(k)
    bb = jax.random.normal(ks[2], (n, m), dtype) if bias else None
    y = ops.pop_matmul(x, w, bb, activation=act, interpret=True)
    yr = ref.pop_matmul_ref(x, w, bb, activation=act)
    assert y.shape == (n, b, m) and y.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL[dtype])


@pytest.mark.parametrize("n,b,k,m,act", _draw_matmul_cases())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pop_matmul_sweep(n, b, k, m, act, dtype):
    _matmul_parity(n, b, k, m, act, dtype)


def test_pop_matmul_no_bias():
    _matmul_parity(2, 16, 8, 8, "relu", jnp.float32, bias=False)


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 6), b=st.integers(1, 64), k=st.integers(1, 64),
       m=st.integers(1, 64), act=st.sampled_from(["none", "relu", "tanh"]),
       bias=st.booleans())
def test_pop_matmul_property(n, b, k, m, act, bias):
    _matmul_parity(n, b, k, m, act, jnp.float32, bias=bias)


def test_supports_shapes():
    """The routing predicate of repro.rl.networks: within-block dims and
    block multiples pass; anything straddling a block boundary is refused
    (the kernel would assert on the tiling)."""
    assert supports_shapes(1, 1, 1)          # everything inside one block
    assert supports_shapes(64, 17, 100)
    assert supports_shapes(256, 128, 384)    # block multiples
    assert not supports_shapes(200, 64, 64)  # 200 > 128, not a multiple
    assert not supports_shapes(64, 130, 64)
    assert not supports_shapes(64, 64, 129)
    assert not supports_shapes(0, 64, 64)    # degenerate


# ------------------------------------------------------------- pop_adam
def _adam_inputs(seed, n, psize, *, zero_grads=False, zero_state=False):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    params = jax.random.normal(ks[0], (n, psize))
    grads = jnp.zeros((n, psize)) if zero_grads \
        else jax.random.normal(ks[1], (n, psize))
    mu = jnp.zeros((n, psize)) if zero_state \
        else jax.random.normal(ks[2], (n, psize)) * 0.1
    nu = jnp.zeros((n, psize)) if zero_state \
        else jnp.abs(jax.random.normal(ks[3], (n, psize))) * 0.01
    return params, grads, mu, nu


def _adam_parity(seed, n, psize, block, lr, step):
    params, grads, mu, nu = _adam_inputs(seed, n, psize)
    p2, m2, v2 = pop_adam(params, grads, mu, nu, lr, step, block=block,
                          interpret=True)
    pr, mr, vr = ref.pop_adam_ref(params, grads, mu, nu, lr, step)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), atol=1e-6)


def _adam_cases():
    # cover P inside one block (odd P included), P an exact multiple of the
    # block, P the kernel pads to a block multiple, and several 8-member
    # row blocks
    cases = [(1, 1, 32), (1, 128, 32), (2, 64, 64), (3, 257, 512),
             (4, 8192, 4096), (3, 300, 128), (16, 300, 128),
             (24, 1000, 256)]
    for _ in range(5):
        n = int(_RNG.integers(1, 7))
        block = int(2 ** _RNG.integers(5, 12))
        if _RNG.integers(2):
            psize = int(_RNG.integers(1, block + 1))     # P <= block
        else:
            psize = block * int(_RNG.integers(1, 5))     # block multiple
        cases.append((n, psize, block))
    return cases


@pytest.mark.parametrize("n,psize,block", _adam_cases())
@pytest.mark.parametrize("step", [1, 7, 10_000])
def test_pop_adam_sweep(n, psize, block, step):
    lr = jnp.linspace(1e-4, 3e-3, n)
    _adam_parity(n * psize + step, n, psize, block,
                 lr, jnp.asarray(step, jnp.int32))


@pytest.mark.parametrize("n", [1, 7, 8, 36, 80, 81, 1001, 4096])
def test_pop_adam_tiles_fit_vmem(n):
    """A tile's VMEM does not grow with the population: rows are 8
    members where 8 divides N, and the lane block shrinks otherwise."""
    rows, lanes = pop_adam_tiles(n, 10 ** 6)
    assert n % rows == 0 and lanes % 128 == 0
    assert rows == (8 if n % 8 == 0 else n)
    assert 7 * 2 * 4 * -(-rows // 8) * 8 * lanes <= VMEM_BUDGET
    assert pop_adam_tiles(n, 100) == (rows, 100)      # P inside one tile


def test_pop_adam_per_member_step():
    """step may be (N,) — members evolve-cloned mid-run disagree on t."""
    _adam_parity(11, 3, 65, 128, jnp.full((3,), 1e-3),
                 jnp.asarray([1, 5, 900], jnp.int32))


def test_pop_adam_lr_zero_is_identity_on_params():
    params, grads, mu, nu = _adam_inputs(5, 2, 33)
    p2, m2, v2 = pop_adam(params, grads, mu, nu, jnp.zeros((2,)),
                          jnp.asarray(3, jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(params))
    # moments still integrate the gradient
    assert float(jnp.max(jnp.abs(m2 - mu))) > 0


def test_pop_adam_zero_grads_zero_state_is_identity():
    params, grads, mu, nu = _adam_inputs(6, 2, 40, zero_grads=True,
                                         zero_state=True)
    p2, m2, v2 = pop_adam(params, grads, mu, nu, jnp.full((2,), 1e-3),
                          jnp.asarray(1, jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(params))
    assert float(jnp.max(jnp.abs(m2))) == 0
    assert float(jnp.max(jnp.abs(v2))) == 0


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 5), raw=st.integers(1, 600),
       block=st.sampled_from([32, 128, 1024]), mult=st.integers(1, 8),
       small=st.booleans(), step=st.integers(1, 10_000),
       scalar_step=st.booleans())
def test_pop_adam_property(n, raw, block, mult, small, step, scalar_step):
    psize = min(raw, block) if small else block * mult
    lr = jnp.linspace(1e-4, 3e-3, n)
    s = jnp.asarray(step, jnp.int32) if scalar_step \
        else jnp.arange(1, n + 1, dtype=jnp.int32) * step
    _adam_parity(seed=step + n + psize, n=n, psize=psize, block=block,
                 lr=lr, step=s)


# ------------------------------------------------------- flash attention
_FLASH_CASES = [(1, 4, 4, 128, 32), (2, 8, 2, 256, 64), (1, 6, 1, 512, 64),
                (1, 1, 1, 128, 16)] + [
    (int(_RNG.integers(1, 3)),) + (lambda g, kv: (g * kv, kv))(
        int(_RNG.integers(1, 4)), int(_RNG.integers(1, 4))) +
    (int(_RNG.choice([128, 256])), int(_RNG.choice([16, 32, 64])))
    for _ in range(4)]


def _flash_parity(b, h, hkv, s, d, dtype, causal=True):
    ks = jax.random.split(jax.random.fold_in(KEY, b * h * s * d), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    orf = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **TOL[dtype])


@pytest.mark.parametrize("b,h,hkv,s,d", _FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, hkv, s, d, dtype):
    _flash_parity(b, h, hkv, s, d, dtype)


def test_flash_attention_non_causal():
    _flash_parity(1, 2, 2, 128, 32, jnp.float32, causal=False)


@needs_hypothesis
@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 2), g=st.integers(1, 3), hkv=st.integers(1, 3),
       s=st.sampled_from([128, 256]), d=st.sampled_from([16, 32, 64]),
       causal=st.booleans())
def test_flash_attention_property(b, g, hkv, s, d, causal):
    _flash_parity(b, g * hkv, hkv, s, d, jnp.float32, causal)


@pytest.mark.parametrize("seq", [128, 256])
def test_lm_forward_through_flash_kernel_matches_sdpa(seq):
    """An LM forward that routes attention through the flash kernel (as
    inference does on a TPU) gives sdpa's logits: the kernel path hands
    the output projection (B, S, H*D), as sdpa does."""
    cfg = get_config("qwen2_0_5b").smoke()
    params = L.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(KEY, (1, seq), 0, cfg.vocab_size)}
    want, _, _ = L.forward(params, cfg, batch)
    got, _, _ = L.forward(params, cfg.replace(use_kernels=True), batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[jnp.float32])


# ------------------------------------------- population-batched applies
def test_pop_linear_jnp_fallback_bitwise_vs_vmap():
    """fused=False lowers to the same dot_general as vmap of the member
    linear — BITWISE.  This equality is the whole fused_linear contract."""
    ks = jax.random.split(KEY, 3)
    n, b, k, m = 4, 9, 7, 11
    p = {"w": jax.random.normal(ks[0], (n, k, m)),
         "b": jax.random.normal(ks[1], (n, m))}
    x = jax.random.normal(ks[2], (n, b, k))
    y = nets.pop_linear_apply(p, x, activation="tanh", fused=False)
    yv = jax.vmap(lambda w, bb, xx: jnp.tanh(xx @ w + bb))(p["w"], p["b"], x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yv))


def test_pop_mlp_jnp_fallback_bitwise_vs_vmap():
    n, b = 3, 6
    params = jax.vmap(lambda k: mlp_init(k, [5, 16, 16, 2]))(
        jax.random.split(KEY, n))
    x = jax.random.normal(jax.random.PRNGKey(3), (n, b, 5))
    y = nets.pop_mlp_apply(params, x, fused=False)
    yv = jax.vmap(mlp_apply)(params, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yv))
    ya = nets.pop_actor_apply(params, x, fused=False)
    np.testing.assert_array_equal(np.asarray(ya),
                                  np.asarray(jnp.tanh(yv)))


@pytest.mark.parametrize("n,b,k,m", [(1, 8, 4, 4), (3, 16, 8, 12),
                                     (2, 128, 128, 128)])
def test_pop_linear_kernel_forward_and_grad(n, b, k, m):
    """The forced-kernel path (interpret off-TPU): forward matches the jnp
    route to tolerance, and jax.grad flows through the custom_vjp with the
    einsum backward (gradients match the fallback's)."""
    ks = jax.random.split(jax.random.fold_in(KEY, 17), 3)
    p = {"w": jax.random.normal(ks[0], (n, k, m)) / np.sqrt(k),
         "b": jax.random.normal(ks[1], (n, m))}
    x = jax.random.normal(ks[2], (n, b, k))
    yf = nets.pop_linear_apply(p, x, activation="tanh", fused=True)
    yj = nets.pop_linear_apply(p, x, activation="tanh", fused=False)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yj),
                               atol=2e-5, rtol=2e-5)

    def loss(params, xx, fused):
        y = nets.pop_linear_apply(params, xx, activation="tanh", fused=fused)
        return jnp.sum(y ** 2)

    gf = jax.grad(loss, argnums=(0, 1))(p, x, True)
    gj = jax.grad(loss, argnums=(0, 1))(p, x, False)
    for a, bb in zip(jax.tree.leaves(gf), jax.tree.leaves(gj)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=2e-4, rtol=2e-4)


def test_pop_linear_untileable_shape_falls_back():
    """fused=True on a shape supports_shapes refuses must still work (the
    auto/forced routes fall back to jnp instead of asserting)."""
    n, b, k, m = 2, 200, 64, 64   # 200 straddles the 128 block
    assert not supports_shapes(b, k, m)
    ks = jax.random.split(KEY, 3)
    p = {"w": jax.random.normal(ks[0], (n, k, m)),
         "b": jax.random.normal(ks[1], (n, m))}
    x = jax.random.normal(ks[2], (n, b, k))
    y = nets.pop_linear_apply(p, x, fused=True)
    yj = nets.pop_linear_apply(p, x, fused=False)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yj))


# ----------------------------------------------- recurrent kernels (kept)
@pytest.mark.parametrize("b,h,s,d,chunk", [(1, 2, 64, 8, 16), (2, 3, 128, 16, 32),
                                           (1, 1, 256, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(b, h, s, d, chunk, dtype):
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (b, h, s, d), dtype) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, d)) * 0.5 - 2.0)
    u = (jax.random.normal(ks[4], (h, d)) * 0.3)
    s0 = jax.random.normal(ks[5], (b, h, d, d)) * 0.1
    y, sf = ops.wkv6(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr, np.float32),
                               **TOL[dtype])
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr, np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("b,h,s,p,n,chunk", [(1, 2, 64, 8, 4, 16),
                                             (2, 4, 128, 16, 8, 32),
                                             (1, 1, 256, 64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(b, h, s, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, h, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, n), dtype)
    cc = jax.random.normal(ks[4], (b, s, n), dtype)
    h0 = jax.random.normal(ks[5], (b, h, p, n)) * 0.1
    y, sf = ops.ssd(x, dt, a, bb, cc, h0, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_ref(x, dt, a, bb, cc, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr, np.float32),
                               **TOL[dtype])
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr, np.float32),
                               **TOL[dtype])


def test_ssd_long_chunk_against_float64():
    """At zamba2's chunk of 256 the in-chunk log-decay cumsums reach
    hundreds; the kernel's segsum must still be as exact as float32 allows,
    here against the scan run in float64."""
    b, h, s, p, n, chunk = 1, 2, 512, 64, 64, 256
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, h, s, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, n))
    cc = jax.random.normal(ks[4], (b, s, n))
    h0 = jax.random.normal(ks[5], (b, h, p, n)) * 0.1
    y, sf = ops.ssd(x, dt, a, bb, cc, h0, chunk=chunk, interpret=True)
    with jax.enable_x64(True):
        f64 = [np.asarray(t, np.float64) for t in (x, dt, a, bb, cc, h0)]
        yr, sr = ref.ssd_scan(np.moveaxis(f64[0], 1, 2),
                              np.moveaxis(f64[1], 1, 2), *f64[2:])
        yr, sr = np.moveaxis(np.asarray(yr), 1, 2), np.asarray(sr)
    assert yr.dtype == np.float64
    # absolute: |y| reaches ~100, where a relative 2e-4 would hide the
    # ~1e-3 a plainly rounded cumsum costs
    np.testing.assert_allclose(np.asarray(y), yr, atol=2e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(sf), sr, atol=2e-4, rtol=0)


def test_grad_accum_equivalence():
    """tcfg.grad_accum microbatching == full-batch step (fp32 accumulate)."""
    from repro.configs import get_config, TrainConfig
    from repro.models import lm as L
    cfg = get_config("qwen2_0_5b").smoke()
    params = L.init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(KEY, (4, 32), 0, cfg.vocab_size)}
    outs = {}
    for ga in (1, 4):
        oi, ts = L.make_train_step(cfg, TrainConfig(
            total_steps=10, warmup_steps=0, grad_accum=ga))
        p2, _, m = jax.jit(ts)(params, oi(params), batch, jnp.asarray(1))
        outs[ga] = (float(m["loss"]), p2)
    assert abs(outs[1][0] - outs[4][0]) < 1e-5
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(outs[1][1]), jax.tree.leaves(outs[4][1])))
    assert err < 1e-4
