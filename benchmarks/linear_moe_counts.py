"""Parameters, bytes and operations of a linear-attention expert
decoder (``models/linear_moe_decoder.py``), from shapes alone.

``cfg`` is a configuration file's dict: the published keys, with
``n_routed_experts`` the experts HELD here and ``published.
n_routed_experts`` the router's width. Counted is the LEAST the
algorithm requires of one decode step over a batch: every weight
outside the routed experts read once (the embedding is a gather of the
batch's rows and not counted), the held experts that got a token read
once each, K and V of the tokens actually resident in the softmax
layers, and every row's state and convolution tail read once and
written once in the KDA layers. How many experts a step touched and
how many tokens were resident is data the engine counts
(``ServingMetrics.experts_touched`` and ``.resident_tokens``); nothing
here guesses them.
"""
from __future__ import annotations


def _lin(cfg):
    lin = cfg["linear_attn_config"]
    return int(lin["num_heads"]), int(lin["head_dim"]), \
        int(lin["short_conv_kernel_size"])


def is_gqa(cfg, i) -> bool:
    if cfg.get("gqa_layers") is not None:
        return i in cfg["gqa_layers"]
    return i % (cfg["gqa_interval"] + 1) == 0


def gqa_layers(cfg) -> int:
    return sum(is_gqa(cfg, i) for i in range(cfg["num_hidden_layers"]))


def kda_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - gqa_layers(cfg)


def router_width(cfg) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def gqa_params(cfg) -> int:
    """q, k, v, the output gate and o."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return c * h * d * (3 if cfg["use_gqa_gate"] else 2) + 2 * c * kv * d


def kda_params(cfg) -> int:
    """qkv and o, the convolution's filters, the two low-rank gates
    (the output gate's with its bias), beta's projection, ``A_log``,
    ``dt_bias`` and the output norm."""
    c = cfg["hidden_size"]
    h, d, taps = _lin(cfg)
    return (4 * c * h * d + taps * 3 * h * d
            + 2 * (c * d + d * h * d) + h * d       # f, g, g's bias
            + c * h + h + h * d + d)                # beta, A_log, dt_bias, norm


def expert_params(cfg) -> int:
    """One routed expert (the shared expert is ``n_shared_experts``
    such): SwiGLU, three projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, i, routed: bool = True) -> int:
    """Decoder layer ``i``; ``routed=False`` leaves the held routed
    experts out (what a decode step reads whatever the routing)."""
    c = cfg["hidden_size"]
    mixer = gqa_params(cfg) if is_gqa(cfg, i) else kda_params(cfg)
    return (mixer + 2 * c + c * router_width(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg)
            + (cfg["n_routed_experts"] * expert_params(cfg) if routed else 0))


def model_params(cfg, routed: bool = True, embedding: bool = True) -> int:
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    return (sum(layer_params(cfg, i, routed)
                for i in range(cfg["num_hidden_layers"]))
            + c * v + c + (c * v if embedding else 0))      # head, norm


def expert_bytes(cfg, itemsize: int = 2) -> int:
    return expert_params(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """K and V of one token over the softmax layers."""
    return (2 * gqa_layers(cfg) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


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
            + resident_tokens * kv_bytes_per_token(cfg, cache_itemsize)
            + kda_step_bytes(cfg, rows))


def kda_chunk_flops(cfg, tokens: int, chunk: int = 64) -> float:
    """Operations the chunked scan's contractions need for ``tokens``
    tokens of one sequence, all KDA layers: a chunk of ``C`` tokens a
    head forms ``A`` and ``P`` (``C^2 d`` each, the causal half),
    solves the unit triangular system (``C^2 d``), applies ``P`` to
    ``U`` (``C^2 d``), and contracts with the carried state three times
    (``2 C d^2`` each). The decays' exponentials and the projections
    around the scan are not counted."""
    h, d, _ = _lin(cfg)
    per_chunk = 4 * chunk * chunk * d + 6 * chunk * d * d
    return float(kda_layers(cfg) * h * (tokens / chunk) * per_chunk)


def decode_flops_per_step(cfg, batch: int, resident_tokens: float) -> float:
    """Operations of one decode step: two a weight a row for every
    matmul a row passes (mixer, router, shared expert, the row's share
    of its ``num_experts_per_tok`` routed experts that is held here,
    the head), softmax attention ``4 x heads x D`` a resident token a
    softmax layer, and the state update ``8 H d^2`` a row a KDA
    layer."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, d, _ = _lin(cfg)
    held_share = cfg["n_routed_experts"] / router_width(cfg)
    per_row = sum(
        layer_params(cfg, i, routed=False)
        + cfg["num_experts_per_tok"] * held_share * expert_params(cfg)
        for i in range(cfg["num_hidden_layers"])) + c * v
    attn = (4.0 * resident_tokens * gqa_layers(cfg)
            * cfg["num_attention_heads"] * cfg["head_dim"])
    return 2.0 * batch * per_row + attn + 8.0 * batch * kda_layers(cfg) \
        * h * d * d
