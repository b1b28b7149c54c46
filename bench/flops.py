"""Operations of a training step, counted from shapes.

Model FLOPs of a dense decoder (the program's dense block: grouped-query
attention, a gated MLP of three matrices, an output head): the matrix
multiplications of the forward pass and of the backward pass (twice the
forward), with causal attention counted as the S(S+1)/2 query-key pairs
it needs. Recomputation under remat does not count, and neither does the
embedding lookup, which is a gather.
"""
from __future__ import annotations


def dense_layer_matmul_params(d_model: int, n_heads: int, n_kv_heads: int,
                              head_dim: int, d_ff: int) -> int:
    """Weights one token multiplies through in one layer."""
    q = d_model * n_heads * head_dim
    kv = 2 * d_model * n_kv_heads * head_dim
    o = n_heads * head_dim * d_model
    mlp = 3 * d_model * d_ff
    return q + kv + o + mlp


def dense_train_step_flops(*, d_model: int, n_heads: int, n_kv_heads: int,
                           head_dim: int, d_ff: int, n_layers: int,
                           vocab: int, batch: int, seq: int) -> int:
    """Forward plus backward FLOPs of one step over ``batch`` x ``seq``."""
    tokens = batch * seq
    per_token = (n_layers * dense_layer_matmul_params(
        d_model, n_heads, n_kv_heads, head_dim, d_ff) + d_model * vocab)
    matmul_fwd = 2 * tokens * per_token
    # QK^T and PV: 2 FLOPs per multiply-add, over causal pairs
    pairs = seq * (seq + 1) // 2
    attn_fwd = n_layers * 2 * 2 * batch * n_heads * head_dim * pairs
    return 3 * (matmul_fwd + attn_fwd)


def config_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """:func:`dense_train_step_flops` for a configuration file."""
    return dense_train_step_flops(
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], n_layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], batch=batch, seq=seq)
