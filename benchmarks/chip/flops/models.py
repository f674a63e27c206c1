"""Model FLOPs from the configuration's widths: what the forward and
backward passes require, with no recomputation counted.

A dense layer of ``a`` inputs and ``b`` outputs costs ``2ab`` per row
forward.  Its backward costs ``2ab`` for the input gradient and ``2ab``
for the weight gradient; a network that is only differentiated through
(its weights are not trained by that loss) pays the input gradient alone.
"""
from __future__ import annotations


def mlp_flops(sizes) -> int:
    """Forward FLOPs per row of an MLP with layer ``sizes``."""
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def td3_update_flops(cfg: dict) -> int:
    """FLOPs of one TD3 update of one member (Fujimoto et al. 2018):
    the critic step every update, the actor step every ``policy_delay``."""
    obs, act, hidden = cfg["obs_dim"], cfg["act_dim"], cfg["hidden"]
    actor = mlp_flops([obs, *hidden, act])
    q = mlp_flops([obs + act, *hidden, 1])
    # target actor and both target critics forward, both online critics
    # forward and backward (weights and inputs)
    critic = actor + 2 * q + 2 * q + 2 * (2 * q)
    # actor forward, Q1 forward, Q1 backward to its input only, actor
    # backward (weights and inputs)
    actor_step = actor + q + q + 2 * actor
    return cfg["batch_size"] * critic + cfg["batch_size"] * actor_step \
        // cfg["policy_delay"]


def td3_epoch_flops(cfg: dict, traffic: dict, *, updating: bool = True) -> int:
    """FLOPs of one fused train-evolve epoch of the whole population:
    acting, the chained updates and the evaluation episodes."""
    actor = mlp_flops([cfg["obs_dim"], *cfg["hidden"], cfg["act_dim"]])
    iters = traffic["pbt_interval"]
    acting = iters * traffic["collect_steps"] * traffic["num_envs"] * actor
    updates = (iters * traffic["updates_per_iter"] * td3_update_flops(cfg)
               if updating else 0)
    evals = (iters // traffic["eval_every"]) * traffic["eval_envs"] \
        * cfg["hopper2d"]["episode_length"] * actor
    return traffic["population"] * (acting + updates + evals)


def lm_active_params(cfg: dict) -> int:
    """Parameters whose matmuls every token pays: all but the embedding
    gather; a tied head counts once, as the output matmul."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    if cfg.get("qkv_bias"):
        attn += h * hd + 2 * hkv * hd
    layer = attn + 3 * d * ff + 2 * d
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d + d


def lm_flops_per_token(cfg: dict, seq_len: int) -> int:
    """6 N_active per trained token plus the attention scores'
    12 L d S (forward and backward of QK^T and PV)."""
    return 6 * lm_active_params(cfg) \
        + 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq_len
