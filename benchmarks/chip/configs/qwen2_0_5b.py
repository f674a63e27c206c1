"""qwen2-0.5b training, built as ``repro.launch.train --arch`` builds it.

One unit of work is one ``PopTrainer.step`` on the traffic's next token
batch: the launcher's LM path (stock Adam under the vectorized backend,
``donate=False``).  The model's sizes come from the configuration file;
the weights come from the benchmark (``reference.qwen2_0_5b.init``),
converted to the program's parameter layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flops.models import lm_flops_per_token
from record import change_norms, first_grad_norms
from reference.qwen2_0_5b import init, root_key
from traffic.lm_tokens import batches

E2E = "tokens_per_s"
CONTROL = "float8_e4m3fn"

# reference layout name -> the program's per-layer parameter path
_LAYER = {"attn_norm": ("attn_norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
          "wq": ("attn", "wq", "w"), "bq": ("attn", "wq", "b"),
          "wk": ("attn", "wk", "w"), "bk": ("attn", "wk", "b"),
          "wv": ("attn", "wv", "w"), "bv": ("attn", "wv", "b"),
          "wo": ("attn", "wo", "w"), "w_gate": ("mlp", "w_gate", "w"),
          "w_up": ("mlp", "w_up", "w"), "w_down": ("mlp", "w_down", "w")}


def to_program(ref):
    """Reference-layout weights -> the program's tree, with the leading
    population axis of one member."""
    layers = {}
    for name, path in _LAYER.items():
        node = layers
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = ref["layers"][name]
    tree = {"embed": {"embedding": ref["embed"]},
            "segments": {"dense": layers},
            "final_norm": {"scale": ref["final_norm"]}}
    return jax.tree.map(lambda x: x[None], tree)


def to_reference(prog):
    """The inverse of :func:`to_program` for member 0."""
    p = jax.tree.map(lambda x: x[0], prog)
    layers = {}
    for name, path in _LAYER.items():
        node = p["segments"]["dense"]
        for k in path:
            node = node[k]
        layers[name] = node
    return {"embed": p["embed"]["embedding"], "layers": layers,
            "final_norm": p["final_norm"]["scale"]}


class Cell:
    def __init__(self, cfg, traffic, seed, spans, *, fault=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.spans = spans
        self.fault = fault
        self.rows = traffic["batch"] * traffic["population"]
        self.work_per_unit = self.rows * traffic["seq_len"]
        self.flops_per_unit = self.work_per_unit * lm_flops_per_token(
            cfg, traffic["seq_len"])
        self.trainer = None

    def lm_config(self):
        from repro.configs import get_config
        c = self.cfg
        return get_config(c["arch"]).replace(
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
            qkv_bias=c["qkv_bias"], activation=c["hidden_act"],
            dtype=c["compute_dtype"])

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
            backend="vectorized", donate=False,
            hyper_space=HyperSpace(
                log_uniform=(("lr_scale", 0.1, 10.0),
                             ("weight_decay", 1e-3, 0.3)),
                uniform=(("warmup_frac", 0.01, 0.25),)))
        key = root_key(self.seed)
        trainer = PopTrainer(LMAgent(self.lm_config(), tcfg), pcfg,
                             key=jax.random.fold_in(key, 1))
        self._make = jax.jit(lambda k: to_program(init(self.cfg, k)))
        params = self._make(key)
        if jax.tree.structure(params) != \
                jax.tree.structure(trainer.state.params):
            raise ValueError("benchmark weights do not match the program's "
                             "parameter tree")
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        st = trainer.state
        trainer.state = st._replace(params=params,
                                    opt_state=zeros(st.opt_state),
                                    step=zeros(st.step))
        self.trainer = trainer
        self.gen = batches(self.cfg["vocab_size"], self.rows,
                           tr["seq_len"], self.seed)
        if self.fault == "unchanged":
            update = trainer._update
            trainer._update = lambda s, b, h: (s, update(s, b, h)[1])
        elif self.fault == "half_batch":
            update = trainer._update

            def half(s, b, h):
                half_rows = b["tokens"].shape[1] // 2
                t = b["tokens"][:, :half_rows]
                return update(s, {"tokens": jnp.concatenate([t, t], 1)}, h)

            trainer._update = half

    def next_batch(self):
        n = self.traffic["population"]
        tokens = jnp.asarray(next(self.gen))
        return {"tokens": tokens.reshape((n, self.traffic["batch"])
                                        + tokens.shape[1:])}

    def dispatch(self):
        with self.spans("next_batch"):
            batch = self.next_batch()
        with self.spans("step_call"):
            metrics, _ = self.trainer.step(batch)
        return metrics["loss"], self.trainer.state

    def wait(self, handle):
        with self.spans("wait"):
            jax.block_until_ready(handle)

    def probe(self, handle):
        return handle[0]

    def setup(self):
        """Build, then drive the first ``check_steps`` steps through the
        window's own call, recording what the comparison reads."""
        self.build()
        losses = []
        b1 = self.cfg["optimizer"]["b1"]
        for step in range(self.traffic["check_steps"]):
            handle = self.dispatch()
            self.wait(handle)
            losses.append(np.asarray(handle[0]).reshape(-1))
            del handle
            if step == 0:
                grad = first_grad_norms(self.trainer.state.opt_state.mu, b1,
                                        view=to_reference)
        change = change_norms(self.trainer.state.params,
                              self._make(root_key(self.seed)),
                              view=to_reference)
        self.record = {"losses": losses, "grad": grad, "change": change}

    def compiled_text(self):
        return None

    def release(self):
        self.trainer = None
        self.gen = None

    def reference(self, dtype="float32"):
        from reference.qwen2_0_5b import run
        return run(self.cfg, self.traffic, self.seed, dtype=dtype)
