"""Parameters, bytes and operations of a KDA / NoPE-MLA expert decoder
(``models/kda_mla_moe_decoder.py``), from shapes alone.

``cfg`` is a configuration file's dict: the published keys, with
``num_experts`` the experts HELD here and ``published.num_experts`` the
router's width; ``linear_attn_config`` numbers the layers from 1.
Counted is the LEAST the algorithm requires of one decode step over a
batch: every weight outside the routed experts read once (the embedding
is a gather of the batch's rows and not counted), the held experts that
got a token read once each, the latent of the tokens actually resident
in the MLA layers as it is stored (``latent_width`` numbers a token:
the 576 of ``[ckv | k_pe]`` in whole lanes), and every row's state and
convolution tail read once and written once in the KDA layers. How many
experts a step touched and how many tokens were resident is data the
engine counts (``ServingMetrics.experts_touched`` and
``.resident_tokens``); nothing here guesses them.
"""
from __future__ import annotations

from benchmarks.linear_moe_counts import (  # noqa: F401
    _lin,
    expert_bytes,
    expert_params,
    kda_params,
)


def is_mla(cfg, i) -> bool:
    return i + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def is_dense(cfg, i) -> bool:
    return i < cfg["first_k_dense_replace"]


def mla_layers(cfg) -> int:
    return sum(is_mla(cfg, i) for i in range(cfg["num_hidden_layers"]))


def kda_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - mla_layers(cfg)


def expert_layers(cfg) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def router_width(cfg) -> int:
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def mla_params(cfg) -> int:
    """q (uncompressed), the latent's projection and norm, its
    expansion to K and V per head, and o."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    kvl = cfg["kv_lora_rank"]
    return (c * h * (dn + dr) + c * (kvl + dr) + kvl
            + kvl * h * (dn + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * c)


def layer_params(cfg, i, routed: bool = True) -> int:
    """Decoder layer ``i`` (from 0); ``routed=False`` leaves the held
    routed experts out (what a decode step reads whatever the
    routing)."""
    c = cfg["hidden_size"]
    mixer = mla_params(cfg) if is_mla(cfg, i) else kda_params(cfg)
    if is_dense(cfg, i):
        return mixer + 2 * c + 3 * c * cfg["intermediate_size"]
    return (mixer + 2 * c + c * router_width(cfg)
            + cfg["num_shared_experts"] * expert_params(cfg)
            + (cfg["num_experts"] * expert_params(cfg) if routed else 0))


def model_params(cfg, routed: bool = True, embedding: bool = True) -> int:
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    return (sum(layer_params(cfg, i, routed)
                for i in range(cfg["num_hidden_layers"]))
            + c * v + c + (c * v if embedding else 0))      # head, norm


def latent_width(cfg) -> int:
    """Numbers a cached token is stored as a layer: ``kv_lora_rank +
    qk_rope_head_dim`` in whole lanes of 128."""
    return 128 * -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128)


def latent_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """The cache of one token over the MLA layers, as stored."""
    return mla_layers(cfg) * latent_width(cfg) * itemsize


def row_state_bytes(cfg, tail_itemsize: int = 2) -> int:
    """What ONE row keeps over the KDA layers: the float32 state and
    the convolution's tail."""
    h, d, taps = _lin(cfg)
    return kda_layers(cfg) * (h * d * d * 4
                              + (taps - 1) * 3 * h * d * tail_itemsize)


def kda_step_bytes(cfg, rows: int) -> int:
    """Least HBM traffic of the one-token state updates of a decode
    step: every row's state and tail read once and written once."""
    return 2 * rows * row_state_bytes(cfg)


def decode_bytes_per_step(cfg, experts_touched: float,
                          resident_tokens: float, rows: int,
                          weight_itemsize: int = 2,
                          cache_itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step."""
    fixed = model_params(cfg, routed=False, embedding=False)
    return (fixed * weight_itemsize
            + experts_touched * expert_bytes(cfg, weight_itemsize)
            + resident_tokens * latent_bytes_per_token(cfg, cache_itemsize)
            + kda_step_bytes(cfg, rows))


def kda_chunk_flops(cfg, tokens: int, chunk: int = 64) -> float:
    """Operations the chunked scan's contractions need for ``tokens``
    tokens of one sequence, all KDA layers (``linear_moe_counts.
    kda_chunk_flops`` has the terms: ``4 C^2 d + 6 C d^2`` a chunk a
    head)."""
    h, d, _ = _lin(cfg)
    per_chunk = 4 * chunk * chunk * d + 6 * chunk * d * d
    return float(kda_layers(cfg) * h * (tokens / chunk) * per_chunk)


def decode_flops_per_step(cfg, batch: int, resident_tokens: float) -> float:
    """Operations of one decode step: two a weight a row for every
    matmul a row passes (mixer, dense FFN or router + shared expert +
    the row's share of its routed experts that is held here, the head),
    absorbed attention ``2 x 2 x heads x latent dims`` a resident token
    an MLA layer, and the state update ``8 H d^2`` a row a KDA layer."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, d, _ = _lin(cfg)
    held_share = cfg["num_experts"] / router_width(cfg)
    per_row = sum(
        layer_params(cfg, i, routed=False)
        + (0 if is_dense(cfg, i) else cfg["num_experts_per_token"]
           * held_share * expert_params(cfg))
        for i in range(cfg["num_hidden_layers"])) + c * v
    attn = (4.0 * resident_tokens * mla_layers(cfg)
            * cfg["num_attention_heads"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))
    return 2.0 * batch * per_row + attn + 8.0 * batch * kda_layers(cfg) \
        * h * d * d
