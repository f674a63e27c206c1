"""Plain reference for ``granite_4_0_h_micro`` training: the Granite 4.0-H
decoder (``model_type`` granitemoehybrid, its published ``config.json``)
written from its layer equations, in float32 with every matmul at
``Precision.HIGHEST``, and AdamW.  It imports nothing of the program.

* ``h = embedding_multiplier * E[ids]``, the embedding tied to the head;
* each layer, of the kind ``layer_types`` gives it: ``h += r * mixer(rms(h))``
  then ``h += r * mlp(rms(h))``, ``r`` the ``residual_multiplier``, the MLP
  SwiGLU;
* Mamba-2 mixer (one group): ``z | xBC | dt = x W_in``; ``xBC`` through a
  causal depthwise convolution of ``mamba_d_conv`` taps with bias, then
  SiLU, split ``x | B | C``; ``dt = softplus(dt + dt_bias)``, ``a =
  -exp(A_log)``; per head the literal recurrence ``h_t = exp(a dt_t)
  h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``; then ``y *
  silu(z)`` through an RMSNorm with its own scale, and ``W_out``;
* attention mixer: grouped-query attention with no bias and no
  positional encoding, causal softmax of ``q k^T * attention_multiplier``;
* ``logits = rms(h) E^T / logits_scaling``, cross-entropy of each position
  against the next token (the last position has no target);
* gradients clipped to a global norm, then AdamW with decoupled weight
  decay on every parameter, on a warmup-cosine schedule.

Departures from the published model, each the benchmark's cut: the layers
are the configuration's first ``num_hidden_layers`` (one whole period),
and the vocabulary is the slice of ``vocab_size`` ids that the traffic
draws from, over which the loss is taken.  So that a training step fits
one chip, the recurrence runs in checkpointed blocks of ``SEQ_BLOCK``
steps, attention in checkpointed blocks of ``QUERY_BLOCK`` queries,
every layer is rematerialised and the loss is taken in sequence chunks;
none of that changes the arithmetic.  ``dtype="float8_e4m3fn"`` rounds
both operands of every linear layer to float8 with a per-tensor scale
(the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BLOCK = 64
QUERY_BLOCK = 512
LOSS_CHUNK = 128
MIXERS = ("mamba", "attention")


def root_key(seed: int):
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jnp.asarray(words, jnp.uint32)


def sizes(cfg):
    """Widths of the Mamba-2 mixer: inner width, heads, head size, state
    size and the convolution's channels (``x``, ``B`` and ``C``)."""
    d_inner = cfg["mamba_expand"] * cfg["hidden_size"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {"d_inner": d_inner, "heads": cfg["mamba_n_heads"],
            "head_dim": cfg["mamba_d_head"], "state": n,
            "conv_ch": d_inner + 2 * groups * n}


def layer_kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def shapes(cfg):
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    m = sizes(cfg)
    kinds = layer_kinds(cfg)
    lm, la = kinds.count("mamba"), kinds.count("attention")
    mlp = lambda n: {"mlp_norm": (n, d), "w_gate": (n, d, ff),
                     "w_up": (n, d, ff), "w_down": (n, ff, d)}
    out = {"embed": (cfg["vocab_size"], d), "final_norm": (d,)}
    if lm:
        out["mamba"] = {
            "norm": (lm, d),
            "in_proj": (lm, d, 2 * m["d_inner"] + 2 * cfg["mamba_n_groups"]
                        * m["state"] + m["heads"]),
            "conv_w": (lm, cfg["mamba_d_conv"], m["conv_ch"]),
            "conv_b": (lm, m["conv_ch"]),
            "a_log": (lm, m["heads"]), "dt_bias": (lm, m["heads"]),
            "d_skip": (lm, m["heads"]), "gate_norm": (lm, m["d_inner"]),
            "out_proj": (lm, m["d_inner"], d), **mlp(lm)}
    if la:
        out["attention"] = {
            "norm": (la, d), "wq": (la, d, h * hd), "wk": (la, d, hkv * hd),
            "wv": (la, d, hkv * hd), "wo": (la, h * hd, d), **mlp(la)}
    return out


def _draw(name, key, s):
    """One leaf's initial values, by its name: norm scales and the skip
    ``D`` 1; ``A_log`` the log of U(1, 16) and ``dt_bias`` the inverse
    softplus of a log-uniform step in [1e-3, 1e-1] (Mamba-2's own
    initialisation); the convolution's weights and bias U(-1/2, 1/2) (a
    4-tap depthwise ``Conv1d``'s default); every other matrix
    N(0, 0.02^2)."""
    if "norm" in name or "d_skip" in name:
        return jnp.ones(s, jnp.float32)
    if "a_log" in name:
        return jnp.log(jax.random.uniform(key, s, jnp.float32, 1.0, 16.0))
    if "dt_bias" in name:
        dt = jnp.exp(jax.random.uniform(key, s, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if "conv" in name:
        return jax.random.uniform(key, s, jnp.float32, -0.5, 0.5)
    return 0.02 * jax.random.normal(key, s, jnp.float32)


def init(cfg, key):
    """Weights from a key; each leaf draws from ``fold_in(key, its
    index)`` (:func:`_draw`)."""
    shp = shapes(cfg)
    is_shape = lambda x: isinstance(x, tuple)
    flat, tree = jax.tree_util.tree_flatten_with_path(shp, is_leaf=is_shape)
    out = [_draw(jax.tree_util.keystr(p), jax.random.fold_in(key, i), s)
           for i, (p, s) in enumerate(flat)]
    return jax.tree.unflatten(tree, out)


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, in the forward
    pass; the gradient passes through in float32."""
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


def _blocks(n, block):
    return block if n % block == 0 else n


def recurrence(x, dt, a, b, c):
    """The SSM's literal recurrence from a zero state.  x: (B, S, H, P),
    dt: (B, S, H), a: (H,), b and c: (B, S, N); returns y: (B, S, H, P)
    without the skip term.  Blocks of ``SEQ_BLOCK`` steps are
    rematerialised, so the backward pass keeps one state per block."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(a * dt_t)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HI)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = _blocks(s, SEQ_BLOCK)
    split = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (s // blk, blk) + (bsz,) + t.shape[2:])
    _, ys = jax.lax.scan(block, jnp.zeros((bsz, h, p, n), jnp.float32),
                         tuple(split(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys.reshape((s, bsz, h, p)), 0, 1)


def _mamba(cfg, mm, x, p):
    bsz, s, _ = x.shape
    m = sizes(cfg)
    di, nh, hp, n = m["d_inner"], m["heads"], m["head_dim"], m["state"]
    zxbcdt = mm(x, p["in_proj"])
    z, xbc = zxbcdt[..., :di], zxbcdt[..., di:di + m["conv_ch"]]
    dt = zxbcdt[..., di + m["conv_ch"]:]
    k = cfg["mamba_d_conv"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + s] * p["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(xbc + p["conv_b"])
    xs = xbc[..., :di].reshape(bsz, s, nh, hp)
    b, c = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(p["a_log"]), b, c) \
        + p["d_skip"][:, None] * xs
    y = y.reshape(bsz, s, di) * jax.nn.silu(z)
    return mm(_rms(y, p["gate_norm"], cfg["rms_norm_eps"]), p["out_proj"])


def _attention(cfg, mm, x, p):
    bsz, s, d = x.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    q = mm(x, p["wq"]).reshape(bsz, s, h, hd)
    k = jnp.repeat(mm(x, p["wk"]).reshape(bsz, s, hkv, hd), h // hkv, 2)
    v = jnp.repeat(mm(x, p["wv"]).reshape(bsz, s, hkv, hd), h // hkv, 2)
    blk = _blocks(s, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qb, first = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) \
            * cfg["attention_multiplier"]
        rows = first + jnp.arange(blk)[:, None]
        scores = jnp.where(rows >= jnp.arange(s)[None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                          precision=HI)

    qs = jnp.moveaxis(q.reshape(bsz, s // blk, blk, h, hd), 1, 0)
    out = jax.lax.map(block, (qs, jnp.arange(0, s, blk)))
    return mm(jnp.moveaxis(out, 0, 1).reshape(bsz, s, h * hd), p["wo"])


def _layer(cfg, mm, kind, x, p):
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    mixer = _mamba if kind == "mamba" else _attention
    x = x + r * mixer(cfg, mm, _rms(x, p["norm"], eps), p)
    y = _rms(x, p["mlp_norm"], eps)
    return x + r * mm(jax.nn.silu(mm(y, p["w_gate"])) * mm(y, p["w_up"]),
                      p["w_down"])


def hidden(cfg, dtype, params, tokens):
    """The final normed hidden state, (B, S, d)."""
    mm = _mm(dtype)
    x = cfg["embedding_multiplier"] * params["embed"][tokens]
    seen = dict.fromkeys(MIXERS, 0)
    for kind in layer_kinds(cfg):
        i = seen[kind]
        seen[kind] += 1
        # the layer takes its weights out of the stack inside the
        # checkpoint, so the backward pass keeps no copy of them
        layer = jax.checkpoint(lambda x, stack, i=i, kind=kind: _layer(
            cfg, mm, kind, x, jax.tree.map(lambda a: a[i], stack)))
        x = layer(x, params[kind])
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def logits(cfg, params, tokens, dtype="float32"):
    """Full logits, (B, S, vocab): for tests at small sizes."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, dtype, params, tokens)
        return _mm(dtype)(x, params["embed"].T) / cfg["logits_scaling"]


def loss_fn(cfg, dtype, params, tokens):
    mm = _mm(dtype)
    x = hidden(cfg, dtype, params, tokens)
    b, s, d = x.shape
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], 1)
    mask = jnp.ones((b, s)).at[:, -1].set(0.0)
    c = _blocks(s, LOSS_CHUNK)
    chunks = lambda t: jnp.moveaxis(t.reshape(b, s // c, c, *t.shape[2:]),
                                    1, 0)

    @jax.checkpoint
    def chunk_ce(args):
        xc, lc, mc = args
        lg = mm(xc, params["embed"].T) / cfg["logits_scaling"]
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
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
    """One AdamW step.  Parameters and moments are donated: two copies of
    them would not fit one chip beside the gradients."""
    opt = cfg["optimizer"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

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

    return jax.jit(step, donate_argnums=(0, 1, 2))


def run(cfg, traffic, seed, *, dtype="float32", steps=None):
    """The record the comparison reads after ``steps`` steps (default
    ``check_steps``) on the traffic's first batches."""
    from record import change_norms, first_grad_norms
    from traffic.lm_tokens import batches
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
