"""granite-4.0-h-micro [hybrid] — Mamba-2 and NoPE GQA mixers, 9 to 1.

40L d_model=2048 32H (GQA kv=8) d_ff=8192 vocab=100352, tied head
[huggingface.co/ibm-granite/granite-4.0-h-micro, config.json].  Layers 5,
15, 25 and 35 attend (no positional encoding, softmax scale 1/64); the
rest are Mamba-2 (64 heads of 64, d_state 128, 1 group, conv 4, expand
2, chunk 256).  Every layer's mixer is followed by a SwiGLU MLP.  muP
multipliers: embeddings x12, each branch x0.22 before its residual add,
logits / 8.  RMSNorm eps 1e-5 throughout.
"""
from repro.configs.base import LMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = LMConfig(
    name="granite-4.0-h-micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=8192, vocab_size=100352, tie_embeddings=True,
    block_type="hybrid", layer_types=_PERIOD * 4,
    ssm_state=128, ssm_head_dim=64, ssm_chunk=256,
    norm_eps=1e-5, position_embedding="nope",
    attention_multiplier=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0,
)
