"""Model FLOPs of a hybrid Mamba-2 / attention decoder (Granite 4.0-H),
from the configuration's widths, with no recomputation counted.

Every parameter but the embedding gather costs 6 FLOPs per trained token
(a tied head counts once, as the output matmul; the depthwise
convolution's taps count as the weights they are).  On top of that come
the two terms whose work grows with the context rather than with the
parameters, forward and backward (3 times the forward):

* attention's scores, ``QK^T`` and ``PV`` over the whole sequence: ``4 S
  H_q d_head`` a token forward;
* SSD's chunked scan (Dao and Gu, 2024, section 6), per chunk of ``Q``
  tokens and per head: the chunk's ``C B^T`` (``2 Q N`` a token, one
  group), its quadratic term against ``x`` (``2 Q P``), the incoming
  state read out by ``C`` (``2 N P``) and the state's update from ``x``
  and ``B`` (``2 N P``).
"""
from __future__ import annotations

import math

import jax

from reference.granite_4_0_h_micro import layer_kinds, shapes


def hybrid_active_params(cfg: dict) -> int:
    """Parameters whose work every token pays: every leaf of the
    reference's weights (each layer's mixer, MLP and two norms, the final
    norm and the tied head, counted once)."""
    return sum(math.prod(s) for s in jax.tree.leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def ssd_flops_per_token(cfg: dict) -> int:
    """Forward FLOPs a token of one Mamba-2 layer's chunked scan."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return 2 * q * n * cfg["mamba_n_groups"] + heads * (2 * q * p + 4 * n * p)


def hybrid_flops_per_token(cfg: dict, seq_len: int) -> int:
    """6 N_active per trained token, plus attention's scores and SSD's
    scan, each 3 times its forward."""
    kinds = layer_kinds(cfg)
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    scores = 4 * seq_len * h * hd * kinds.count("attention")
    ssd = ssd_flops_per_token(cfg) * kinds.count("mamba")
    return 6 * hybrid_active_params(cfg) + 3 * (scores + ssd)
