"""Plain reference for ``qwen2_0_5b`` training: the Qwen2 decoder
(arXiv:2407.10671) from its published description, in float32 with every
matmul at ``Precision.HIGHEST``, and AdamW.  It imports nothing of the
program.

* token embedding, tied to the output head;
* per layer: RMSNorm, grouped-query attention (query heads share key and
  value heads in consecutive groups) with biases on q, k and v, rotary
  positions (rotate-half, base ``rope_theta``), causal softmax; RMSNorm,
  SwiGLU feed-forward; both residual;
* final RMSNorm, logits against the embedding, cross-entropy of each
  position against the next token (the last position has no target);
* gradients clipped to a global norm, then AdamW with decoupled weight
  decay on every parameter, on a warmup-cosine schedule.

Layers are rematerialised and the loss is taken in sequence chunks so
that it fits beside its optimizer state; that changes no arithmetic.
``dtype="float8_e4m3fn"`` rounds both operands of every linear layer to
float8 with a per-tensor scale (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from record import change_norms, first_grad_norms
from traffic.lm_tokens import batches

HI = jax.lax.Precision.HIGHEST
LOSS_CHUNK = 128


def root_key(seed: int):
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jnp.asarray(words, jnp.uint32)


def shapes(cfg):
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    return {"embed": (cfg["vocab_size"], d),
            "layers": {"attn_norm": (L, d), "wq": (L, d, h * hd),
                       "bq": (L, h * hd), "wk": (L, d, hkv * hd),
                       "bk": (L, hkv * hd), "wv": (L, d, hkv * hd),
                       "bv": (L, hkv * hd), "wo": (L, h * hd, d),
                       "mlp_norm": (L, d), "w_gate": (L, d, ff),
                       "w_up": (L, d, ff), "w_down": (L, ff, d)},
            "final_norm": (d,)}


def init(cfg, key):
    """Weights from a key: matrices and biases N(0, 0.02^2), norm scales
    1.  Each leaf draws from ``fold_in(key, its index)``."""
    shp = shapes(cfg)
    leaves, tree = jax.tree.flatten(shp, is_leaf=lambda x: isinstance(x,
                                                                      tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shp, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (name, s) in enumerate(zip(names, leaves)):
        if "norm" in name:
            out.append(jnp.ones(s, jnp.float32))
        else:
            out.append(0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                                s, jnp.float32))
    return jax.tree.unflatten(tree, out)


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, in the forward
    pass; the gradient passes through in float32, as fp8 training keeps
    its cotangents wider than its operands."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(dtype):
    if dtype == "float8_e4m3fn":
        return lambda x, w: jnp.matmul(_fp8(x), _fp8(w), precision=HI)
    return lambda x, w: jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D); rotate-half pairs (i, i + D/2)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, mm, x, p):
    b, s, d = x.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // h, cfg["rms_norm_eps"]
    y = _rms(x, p["attn_norm"], eps)
    q = (mm(y, p["wq"]) + p["bq"]).reshape(b, s, h, hd)
    k = (mm(y, p["wk"]) + p["bk"]).reshape(b, s, hkv, hd)
    v = (mm(y, p["wv"]) + p["bv"]).reshape(b, s, hkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision=HI).reshape(b, s, h * hd)
    x = x + mm(att, p["wo"])
    y = _rms(x, p["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(y, p["w_gate"])) * mm(y, p["w_up"]),
                  p["w_down"])


def loss_fn(cfg, dtype, params, tokens):
    mm = _mm(dtype)
    x = params["embed"][tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(cfg, mm, x, p), None))
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    b, s, d = x.shape
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], 1)
    mask = jnp.ones((b, s)).at[:, -1].set(0.0)
    c = LOSS_CHUNK if s % LOSS_CHUNK == 0 else s
    chunks = lambda t: jnp.moveaxis(t.reshape(b, s // c, c, *t.shape[2:]),
                                    1, 0)

    @jax.checkpoint
    def chunk_ce(args):
        xc, lc, mc = args
        logits = mm(xc, params["embed"].T)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return jnp.sum((logz - gold) * mc)

    ce = jax.lax.map(chunk_ce, (chunks(x), chunks(labels), chunks(mask)))
    return jnp.sum(ce) / jnp.sum(mask)


def lr_at(opt, step):
    base, warm = opt["lr"], opt["warmup_steps"]
    span = max(opt["total_steps"] - warm, 1)
    t = min(max(step - warm, 0), span) / span
    cos = base * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * t)))
    return base * step / max(warm, 1) if step < warm else cos


def make_step(cfg, dtype):
    opt = cfg["optimizer"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

    @jax.jit
    def step(params, mu, nu, t, lr, tokens):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda p: loss_fn(cfg, dtype, p, tokens))(params)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, opt["max_grad_norm"]
                                      / (norm + 1e-9)), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
            - lr * opt["weight_decay"] * p, params, mu, nu)
        return params, mu, nu, loss

    return step


def run(cfg, traffic, seed, *, dtype="float32", steps=None):
    """The record the comparison reads after ``steps`` steps (default
    ``check_steps``) on the traffic's first batches."""
    steps = steps or traffic["check_steps"]
    rows = traffic["batch"] * traffic["population"]
    gen = batches(cfg["vocab_size"], rows, traffic["seq_len"], seed)
    key = root_key(seed)
    params = jax.jit(lambda k: init(cfg, k))(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    step = make_step(cfg, dtype)
    losses, grad = [], None
    for s in range(steps):
        tokens = jnp.asarray(next(gen)[:traffic["batch"]])
        params, mu, nu, loss = step(params, mu, nu, jnp.float32(s + 1),
                                    jnp.float32(lr_at(cfg["optimizer"], s)),
                                    tokens)
        losses.append(np.asarray([loss]))
        if s == 0:
            grad = first_grad_norms(mu, cfg["optimizer"]["b1"])
    del mu, nu
    init_params = jax.jit(lambda k: init(cfg, k))(key)
    return {"losses": losses, "grad": grad,
            "change": change_norms(params, init_params)}
