"""granite-4.0-h-micro training, built as ``repro.launch.train --arch``
builds it.

One unit of work is one ``PopTrainer.step`` on the traffic's next token
batch: the launcher's LM path (stock Adam under the vectorized backend),
with the population's buffers donated to the step (``donate=True``, the
default; the launcher turns it off only for its asynchronous checkpoints,
which a cell does not keep).  At 16 B a parameter the state and the
gradients take 12.35 GB of the chip's 16; two copies of the state would
not fit.  The model's sizes come from the configuration file; the weights
come from the benchmark (``reference.granite_4_0_h_micro.init``),
converted to the program's parameter layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flops.hybrid import hybrid_flops_per_token
from record import change_norms, first_grad_norms
from reference.granite_4_0_h_micro import MIXERS, init, layer_kinds, root_key
from traffic.lm_tokens import batches

E2E = "tokens_per_s"
CONTROL = "float8_e4m3fn"

# reference layout name -> the program's per-layer parameter path
_MLP = {"norm": ("norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
        "w_gate": ("mlp", "w_gate", "w"), "w_up": ("mlp", "w_up", "w"),
        "w_down": ("mlp", "w_down", "w")}
_LAYER = {
    "mamba": {**_MLP, "in_proj": ("mixer", "in_proj", "w"),
              "conv_w": ("mixer", "conv", "w"),
              "conv_b": ("mixer", "conv", "b"), "a_log": ("mixer", "a_log"),
              "dt_bias": ("mixer", "dt_bias"), "d_skip": ("mixer", "d_skip"),
              "gate_norm": ("mixer", "norm", "scale"),
              "out_proj": ("mixer", "out_proj", "w")},
    "attention": {**_MLP, "wq": ("mixer", "wq", "w"),
                  "wk": ("mixer", "wk", "w"), "wv": ("mixer", "wv", "w"),
                  "wo": ("mixer", "wo", "w")},
}


def to_program(ref, cfg):
    """Reference-layout weights -> the program's tree, with the leading
    population axis of one member.  The reference stacks each mixer's
    layers in order; the program stacks them per period of its scan."""
    from repro.models.lm import layout
    count = layout(lm_config(cfg))[0].count
    segment = {}
    for kind in MIXERS:
        if kind not in ref:
            continue
        node = segment.setdefault(kind, {})
        for name, path in _LAYER[kind].items():
            leaf = ref[kind][name]
            n = node
            for k in path[:-1]:
                n = n.setdefault(k, {})
            n[path[-1]] = leaf.reshape((count, -1) + leaf.shape[1:])
    tree = {"embed": {"embedding": ref["embed"]},
            "segments": {"hybrid": segment},
            "final_norm": {"scale": ref["final_norm"]}}
    return jax.tree.map(lambda x: x[None], tree)


def to_reference(prog):
    """The inverse of :func:`to_program` for member 0."""
    p = jax.tree.map(lambda x: x[0], prog)
    out = {"embed": p["embed"]["embedding"],
           "final_norm": p["final_norm"]["scale"]}
    for kind, stack in p["segments"]["hybrid"].items():
        out[kind] = {}
        for name, path in _LAYER[kind].items():
            node = stack
            for k in path:
                node = node[k]
            out[kind][name] = node.reshape((-1,) + node.shape[2:])
    return out


def lm_config(c):
    """The program's configuration from the file's published keys."""
    from repro.configs import get_config
    d = c["hidden_size"]
    if (c["mamba_n_groups"], c["mamba_d_conv"], c["mamba_expand"]) != \
            (1, 4, 2) or c["mamba_n_heads"] * c["mamba_d_head"] != 2 * d:
        raise ValueError("the program's Mamba-2 has one group, 4 taps and "
                         "an expansion of 2")
    return get_config(c["arch"]).replace(
        num_layers=c["num_hidden_layers"], d_model=d,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        qkv_bias=c["attention_bias"], activation=c["hidden_act"],
        layer_types=tuple(layer_kinds(c)), ssm_state=c["mamba_d_state"],
        ssm_head_dim=c["mamba_d_head"], ssm_chunk=c["mamba_chunk_size"],
        norm_eps=c["rms_norm_eps"],
        position_embedding=c["position_embedding_type"],
        attention_multiplier=c["attention_multiplier"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=float(c["logits_scaling"]), dtype=c["compute_dtype"])


class Cell:
    def __init__(self, cfg, traffic, seed, spans, *, fault=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.spans = spans
        self.fault = fault
        self.rows = traffic["batch"] * traffic["population"]
        self.work_per_unit = self.rows * traffic["seq_len"]
        self.flops_per_unit = self.work_per_unit * hybrid_flops_per_token(
            cfg, traffic["seq_len"])
        self.trainer = None

    def build(self):
        from repro.configs import TrainConfig
        from repro.configs.base import HyperSpace, PopulationConfig
        from repro.pop import LMAgent, PopTrainer

        opt, tr = self.cfg["optimizer"], self.traffic
        tcfg = TrainConfig(lr=opt["lr"], total_steps=opt["total_steps"],
                           warmup_steps=opt["warmup_steps"],
                           weight_decay=opt["weight_decay"],
                           max_grad_norm=opt["max_grad_norm"])
        pcfg = PopulationConfig(
            size=tr["population"], strategy=tr["strategy"],
            backend="vectorized", donate=True,
            hyper_space=HyperSpace(
                log_uniform=(("lr_scale", 0.1, 10.0),
                             ("weight_decay", 1e-3, 0.3)),
                uniform=(("warmup_frac", 0.01, 0.25),)))
        key = root_key(self.seed)
        agent = LMAgent(lm_config(self.cfg), tcfg)
        make = self._make = jax.jit(lambda k: to_program(init(self.cfg, k),
                                                         self.cfg))

        own_init = agent.population_init

        def seeded(k, n):
            """The benchmark's weights and zero moments in place of the
            trainer's own initial state, which is never made: both would
            not fit."""
            like = jax.eval_shape(lambda k: own_init(k, n), k)
            params = make(key)
            if jax.tree.structure(params) != jax.tree.structure(like.params):
                raise ValueError("benchmark weights do not match the "
                                 "program's parameter tree")
            opt_state, step = jax.jit(lambda: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                (like.opt_state, like.step)))()
            return like._replace(params=params, opt_state=opt_state,
                                 step=step)

        agent.population_init = seeded
        trainer = PopTrainer(agent, pcfg, key=jax.random.fold_in(key, 1))
        self.trainer = trainer
        self.gen = batches(self.cfg["vocab_size"], self.rows,
                           tr["seq_len"], self.seed)
        if self.fault == "unchanged":
            update = trainer._update
            # the donated state comes back as it went in: a copy of it
            # would not fit beside the step
            trainer._update = jax.jit(
                lambda s, b, h: (s, update(s, b, h)[1]), donate_argnums=0)
        elif self.fault == "half_batch":
            update = trainer._update

            def half(s, b, h):
                half_len = b["tokens"].shape[-1] // 2
                t = b["tokens"][..., :half_len]
                return update(s, {"tokens": jnp.concatenate([t, t], -1)}, h)

            trainer._update = half

    def next_batch(self):
        n = self.traffic["population"]
        tokens = jnp.asarray(next(self.gen))
        return {"tokens": tokens.reshape((n, self.traffic["batch"])
                                        + tokens.shape[1:])}

    def dispatch(self):
        """One step; its handle is the loss, since the state it returns is
        donated to the next step."""
        with self.spans("next_batch"):
            batch = self.next_batch()
        with self.spans("step_call"):
            metrics, _ = self.trainer.step(batch)
        return metrics["loss"]

    def wait(self, handle):
        with self.spans("wait"):
            jax.block_until_ready(handle)

    def probe(self, handle):
        return handle

    def setup(self):
        """Build, then drive the first ``check_steps`` steps through the
        window's own call, recording what the comparison reads."""
        self.build()
        losses = []
        b1 = self.cfg["optimizer"]["b1"]
        for step in range(self.traffic["check_steps"]):
            handle = self.dispatch()
            self.wait(handle)
            losses.append(np.asarray(handle).reshape(-1))
            if step == 0:
                grad = first_grad_norms(self.trainer.state.opt_state.mu, b1,
                                        view=to_reference)
        change = change_norms(self.trainer.state.params,
                              self._make(root_key(self.seed)),
                              view=to_reference)
        self.record = {"losses": losses, "grad": grad, "change": change}

    def compiled_text(self):
        """Compiled HLO text of one unit: the trainer's update at the
        traffic's batch shape (a persistent-cache read of the program
        set-up compiled)."""
        t, tr = self.trainer, self.traffic
        batch = {"tokens": jax.ShapeDtypeStruct(
            (tr["population"], tr["batch"], tr["seq_len"]), jnp.int32)}
        return t._update.lower(t.state, batch, t.hypers).compile().as_text()

    def release(self):
        """Free the device state now: the reference that runs next needs
        the chip's memory."""
        for x in jax.tree.leaves(self.trainer.state):
            x.delete()
        self.trainer = None
        self.gen = None

    def reference(self, dtype="float32"):
        from reference.granite_4_0_h_micro import run
        return run(self.cfg, self.traffic, self.seed, dtype=dtype)
