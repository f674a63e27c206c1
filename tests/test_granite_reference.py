"""granite-4.0-h-micro against its plain float32 reference
(``benchmarks/chip/reference/granite_4_0_h_micro.py``) at a small size on
seeded random weights: logits, loss and every gradient.

The program runs in float32 here, and the CPU computes float32 matmuls in
full, so the two differ only where they take different routes to the same
sums: the program's chunked SSD scan against the reference's step-by-step
recurrence, its attention in grouped heads against repeated ones, its
loss in one pass against the reference's chunks.  Measured on this size,
the largest of those differences is about 1e-6 of the largest value
compared; ``TOL`` allows 1e-4, a hundred times that.  A wrong softmax
scale, positions put back into q and k, or any of the four muP
multipliers left out moves the logits by at least ten times ``TOL``
(``test_each_published_mechanism_is_seen``).
"""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm as L

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.append(str(CHIP))  # what the benchmark's modules import

TOL = 1e-4


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, CHIP / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference/granite_4_0_h_micro.py", "granite_reference")
CELL = _load("configs/granite_4_0_h_micro.py", "granite_cell")

# the published configuration at a small size: two periods of
# mamba, attention, mamba; 8 Mamba heads of 16 over a state of 8 in
# chunks of 8, so a sequence of 32 spans four chunks
SMALL = dict(
    json.loads((CHIP / "configs" / "granite_4_0_h_micro.json").read_text()),
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, vocab_size=96, num_hidden_layers=6,
    layer_types=["mamba", "attention", "mamba"] * 2, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
    compute_dtype="float32")
B, S = 2, 32


# the reference's initialisation with its matrices widened, so that no
# branch is lost in the others: N(0, 0.1^2), and q and k N(0, 0.8^2) so
# that attention's scores (scaled by 1/64) are far from uniform
WIDEN = {"in_proj": 5.0, "out_proj": 5.0, "wv": 5.0, "wo": 5.0,
         "w_gate": 5.0, "w_up": 5.0, "w_down": 5.0, "embed": 5.0,
         "wq": 40.0, "wk": 40.0}


def _weights():
    params = REF.init(SMALL, REF.root_key(7))
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x * WIDEN.get(jax.tree_util.keystr(p).split("'")[-2],
                                   1.0), params)


@pytest.fixture(scope="module")
def setup():
    cfg = CELL.lm_config(SMALL)
    ref = _weights()
    prog = jax.tree.map(lambda x: x[0], CELL.to_program(ref, SMALL))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                SMALL["vocab_size"])
    return cfg, ref, prog, tokens


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_logits_match_reference(setup):
    cfg, ref, prog, tokens = setup
    got, _, _ = L.forward(prog, cfg, {"tokens": tokens})
    want = REF.logits(SMALL, ref, tokens)
    assert _gap(got, want) < TOL


def test_loss_and_every_gradient_match_reference(setup):
    cfg, ref, prog, tokens = setup
    (loss, _), g_prog = jax.value_and_grad(
        lambda p: L.lm_loss(p, cfg, {"tokens": tokens}), has_aux=True)(prog)
    with jax.default_matmul_precision("highest"):
        want, g_ref = jax.value_and_grad(
            lambda p: REF.loss_fn(SMALL, "float32", p, tokens))(ref)
    assert abs(float(loss) - float(want)) < TOL * abs(float(want))
    got = CELL.to_reference(jax.tree.map(lambda x: x[None], g_prog))
    names = lambda t: {jax.tree_util.keystr(p): x for p, x in
                       jax.tree_util.tree_flatten_with_path(t)[0]}
    got, g_ref = names(got), names(g_ref)
    assert set(got) == set(g_ref)
    gaps = {k: _gap(got[k], g_ref[k]) for k in g_ref}
    assert max(gaps.values()) < TOL, gaps


@pytest.mark.parametrize("change", [
    {"attention_multiplier": None},       # 1/sqrt(head_dim)
    {"position_embedding": "rope"},
    {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0},
    {"logits_scaling": 1.0},
])
def test_each_published_mechanism_is_seen(setup, change):
    cfg, ref, prog, tokens = setup
    got, _, _ = L.forward(prog, cfg.replace(**change), {"tokens": tokens})
    assert _gap(got, REF.logits(SMALL, ref, tokens)) > 10 * TOL


def test_reference_recurrence_matches_a_python_loop():
    """The reference's blocked, rematerialised recurrence against the
    equations one step at a time in float64."""
    rng = np.random.default_rng(0)
    b, s, h, p, n = 1, 2 * REF.SEQ_BLOCK, 2, 3, 4
    x = rng.normal(size=(b, s, h, p))
    dt = rng.uniform(0.01, 0.5, size=(b, s, h))
    a = -rng.uniform(0.5, 2.0, size=(h,))
    bb, cc = rng.normal(size=(b, s, n)), rng.normal(size=(b, s, n))
    state = np.zeros((b, h, p, n))
    want = np.zeros_like(x)
    for t in range(s):
        state = (np.exp(a * dt[:, t])[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None]
                 * bb[:, t, None, None])
        want[:, t] = np.einsum("bhpn,bn->bhp", state, cc[:, t])
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    got = REF.recurrence(f32(x), f32(dt), f32(a), f32(bb), f32(cc))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
