"""Operation and byte counts against hand counts at small shapes."""
from flops.kernels import kernel_costs, pop_adam_cost, pop_matmul_cost
from flops.models import (lm_active_params, lm_flops_per_token, mlp_flops,
                          td3_epoch_flops, td3_update_flops)

TD3 = {"obs_dim": 2, "act_dim": 1, "hidden": [4], "batch_size": 3,
       "policy_delay": 2, "hopper2d": {"episode_length": 5}}


def test_mlp_flops_by_hand():
    # 2x4 and 4x1 layers: 2*8 + 2*4 multiply-adds per row
    assert mlp_flops([2, 4, 1]) == 24


def test_td3_update_by_hand():
    actor = 2 * (2 * 4 + 4 * 1)            # 24
    q = 2 * (3 * 4 + 4 * 1)                # 32
    critic = actor + 4 * q + 4 * q         # targets, online fwd, online bwd
    actor_step = actor + 2 * q + 2 * actor
    assert td3_update_flops(TD3) == 3 * critic + 3 * actor_step // 2


def test_td3_epoch_by_hand():
    traffic = {"population": 2, "pbt_interval": 4, "collect_steps": 3,
               "num_envs": 1, "updates_per_iter": 2, "eval_every": 2,
               "eval_envs": 1}
    actor = 24
    per_member = (4 * 3 * 1 * actor + 4 * 2 * td3_update_flops(TD3)
                  + 2 * 1 * 5 * actor)
    assert td3_epoch_flops(TD3, traffic) == 2 * per_member


LM = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
      "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 10,
      "qkv_bias": True}


def test_lm_params_by_hand():
    # q 8x8 + k 8x4 + v 8x4 + o 8x8, biases 8 + 4 + 4, mlp 3 * 8x16,
    # two norms of 8; head 10x8 (tied, counted once); final norm 8
    layer = 64 + 32 + 32 + 64 + 16 + 384 + 16
    assert lm_active_params(LM) == 2 * layer + 80 + 8


def test_lm_flops_per_token_by_hand():
    assert lm_flops_per_token(LM, 5) == 6 * lm_active_params(LM) \
        + 12 * 2 * 8 * 5


def test_pop_matmul_call_by_hand():
    res = [("f32", (2, 3, 5))]
    ops = [("f32", (2, 3, 4)), ("f32", (2, 4, 5)), ("f32", (2, 1, 5))]
    flops, nbytes = pop_matmul_cost(res, ops)
    assert flops == 2 * 2 * 3 * 4 * 5 + 2 * 3 * 5
    assert nbytes == 4 * (24 + 40 + 10 + 30)


def test_pop_adam_call_by_hand():
    col = ("f32", (2, 1))
    mat = ("f32", (2, 6))
    flops, nbytes = pop_adam_cost([mat] * 3, [col] * 3 + [mat] * 4)
    assert flops == 13 * 12
    assert nbytes == 4 * (3 * 2 + 7 * 12)


HLO = """
  %pop_matmul.1 = f32[20,256,256]{2,1,0:T(8,128)} custom-call(%copy, %w.1, %reshape.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[20,256,14]{2,1,0}, f32[20,14,256]{2,1,0}, f32[20,1,256]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="x"}
  %pop_adam.1 = (f32[20,128]{1,0}, f32[20,128]{1,0}, f32[20,128]{1,0}) custom-call(%a, %b, %c, %p, %g, /*index=5*/%m, %v), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[20,1]{1,0}, f32[20,1]{1,0}, f32[20,1]{1,0}, f32[20,128]{1,0}, f32[20,128]{1,0}, f32[20,128]{1,0}, f32[20,128]{1,0}}, metadata={op_name="y"}
  %fusion.3 = f32[4]{0} fusion(%p0), kind=kLoop
"""


def test_costs_from_compiled_text():
    mm = kernel_costs(HLO, "pop_matmul")
    assert list(mm) == ["pop_matmul.1"]
    assert mm["pop_matmul.1"][0] == 2 * 20 * 256 * 14 * 256 + 20 * 256 * 256
    adam = kernel_costs(HLO, "pop_adam")
    assert adam["pop_adam.1"] == (13 * 20 * 128,
                                  4 * (3 * 20 + 7 * 20 * 128))
