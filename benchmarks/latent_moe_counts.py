"""Parameters, bytes and operations of a latent-attention expert
decoder (``models/latent_moe_decoder.py``), from shapes alone.

``cfg`` is a configuration file's dict (the published keys). Counted is
what the algorithm requires of ONE decode step over a batch: every
weight outside the routed experts read once (the embedding is a
gather of the batch's rows and not counted), the experts that got a
token read once each, and the latent cache of the tokens actually
resident. How many experts a step touched and how many tokens were
resident is data the engine counts (``ServingMetrics.experts_touched``
and ``.resident_tokens``); nothing here guesses them.
"""
from __future__ import annotations


def attention_params(cfg) -> int:
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kvl, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (c * ql + ql * h * dq + c * (kvl + cfg["qk_rope_head_dim"])
            + kvl * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * c + ql + kvl)


def hc_params(cfg) -> int:
    """The mHC maps of one layer (two sub-layers): ``phi``, the bias
    and the three gates of each."""
    n = cfg["hc_mult"]
    width = 2 * n + n * n
    return 2 * (n * cfg["hidden_size"] * width + width + 3)


def expert_params(cfg) -> int:
    """One routed expert (the shared expert is ``n_shared_experts``
    such): SwiGLU, three projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, dense: bool, routed: bool = True) -> int:
    """One decoder layer; ``routed=False`` leaves the routed experts
    out (what a decode step reads whatever the routing)."""
    c = cfg["hidden_size"]
    base = attention_params(cfg) + hc_params(cfg) + 2 * c   # two norms
    if dense:
        return base + 3 * c * cfg["intermediate_size"]
    e = cfg["n_routed_experts"]
    return (base + c * e + e                                # router, e_bias
            + cfg["n_shared_experts"] * expert_params(cfg)
            + (e * expert_params(cfg) if routed else 0))


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def model_params(cfg, routed: bool = True, embedding: bool = True) -> int:
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    dense = cfg["first_k_dense_replace"]
    return (dense * layer_params(cfg, True)
            + expert_layers(cfg) * layer_params(cfg, False, routed)
            + c * v + c + (c * v if embedding else 0))      # head, norm


def latent_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """The cache of one token over all layers: ``kv_lora_rank +
    qk_rope_head_dim`` numbers a layer."""
    return (cfg["num_hidden_layers"] * itemsize
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))


def expert_bytes(cfg, itemsize: int = 2) -> int:
    return expert_params(cfg) * itemsize


def decode_bytes_per_step(cfg, experts_touched: float,
                          resident_tokens: float, weight_itemsize: int = 2,
                          cache_itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: the weights outside the
    routed experts once, each touched expert once (``experts_touched``
    summed over the expert layers), and the latent of the resident
    tokens."""
    fixed = model_params(cfg, routed=False, embedding=False)
    return (fixed * weight_itemsize
            + experts_touched * expert_bytes(cfg, weight_itemsize)
            + resident_tokens * latent_bytes_per_token(cfg, cache_itemsize))


def decode_flops_per_step(cfg, batch: int, resident_tokens: float) -> float:
    """Operations of one decode step: two a weight a row for every
    matmul a row passes (the attention and mHC maps, router, shared and
    ``num_experts_per_tok`` routed experts or the dense FFN, the head),
    and absorbed attention, ``2 x 2 x heads x latent dims`` a resident
    token a layer."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    n = cfg["hc_mult"]
    per_row_layer = (attention_params(cfg)
                     + 2 * n * c * (2 * n + n * n))
    dense = per_row_layer + 3 * c * cfg["intermediate_size"]
    expert = (per_row_layer + c * cfg["n_routed_experts"]
              + (cfg["n_shared_experts"] + cfg["num_experts_per_tok"])
              * expert_params(cfg))
    rows = 2.0 * batch * (cfg["first_k_dense_replace"] * dense
                          + expert_layers(cfg) * expert + c * v)
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    attn = (4.0 * resident_tokens * cfg["num_hidden_layers"]
            * cfg["num_attention_heads"] * latent)
    return rows + attn
