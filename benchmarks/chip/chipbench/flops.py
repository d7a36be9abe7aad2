"""Operations the benchmark's work requires, from shapes alone.

Model FLOPs count what the algorithm needs: recomputation (remat) does
not count, and an embedding lookup is a gather, not a matrix product.
"""
from __future__ import annotations


def dense_lm_matmul_params(cfg):
    """Weights that take part in a matrix product per token: every
    projection of every layer and the output head (the head counts even
    when it is tied to the embedding).  ``cfg`` uses the published
    config's keys."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    return L * (attn + mlp) + d * cfg["vocab_size"]


def dense_lm_train_flops_per_token(cfg, seq_len):
    """Forward and backward FLOPs per token of a causal dense LM.

    6 per matrix-product weight (2 forward, 4 backward), plus causal
    attention: forward Q·K^T and P·V take 2·S·H·D each per token over
    the full square, causality halves that, and the backward doubles
    it again: 6·L·S·H·D in all."""
    attn = (6 * cfg["num_hidden_layers"] * seq_len
            * cfg["num_attention_heads"] * cfg["head_dim"])
    return 6 * dense_lm_matmul_params(cfg) + attn

