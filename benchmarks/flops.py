"""Operations and bytes a dense decoder's step needs, from shapes alone.

``cfg`` is a configuration file's dict (the published keys). Counted is
what the algorithm requires: recomputation is not, the embedding lookup
is a gather and not a matmul, and attention is causal.
"""
from __future__ import annotations


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    heads = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads") or heads
    attn = h * heads * d + 2 * h * kv * d + heads * d * h
    mlp = 3 * h * cfg["intermediate_size"]
    return attn + mlp


def matmul_params(cfg) -> int:
    """Parameters that multiply activations: every layer's projections
    and the output head; not the embedding table, not the norms."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward: 6 a matmul parameter, and causal attention's
    score and value products, 6 * L * hidden * S a token."""
    attn = 6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * head_dim(cfg) * seq
    return 6 * matmul_params(cfg) + attn


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * batch * seq


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    kv = cfg.get("num_key_value_heads") or cfg["num_attention_heads"]
    return 2 * cfg["num_hidden_layers"] * kv * head_dim(cfg) * itemsize


def decode_bytes_per_step(cfg, resident_tokens: float,
                          weight_itemsize: int = 2,
                          kv_itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every matmul weight read
    once, and the keys and values of the tokens actually resident."""
    return (matmul_params(cfg) * weight_itemsize
            + resident_tokens * kv_bytes_per_token(cfg, kv_itemsize))
