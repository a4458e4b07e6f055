"""Kimi-Linear decoder: a stack whose layers choose (mixer) x (FFN)
from the config. Three layers in four mix tokens by KDA (Kimi Delta
Attention, arXiv 2510.26692) and keep a float32 STATE a row; every
fourth is latent attention (MLA) WITHOUT rope and without query
compression and keeps ONE latent vector a token. The first layer's FFN
is dense; every other layer's is sigmoid-routed experts with one shared
expert, and the layer can be told which experts it holds.

Written from the public ``config.json`` of
``Kimi-Linear-48B-A3B-Instruct`` (``model_type`` ``kimi_linear``); the
fields below keep its names. Pre-norm residual layers, final RMSNorm,
untied head. Every part is another family's, imported and not copied:

- **KDA** (``linear_attn_config.kda_layers``, numbered from 1 as
  published): ``solar_open2.SolarOpen2KDA``, its three forms and its
  ``length`` freezing, with ``beta = sigmoid(x W_b)`` (no
  ``kda_allow_neg_eigval`` in this family: factor 1).
- **MLA, NoPE** (``linear_attn_config.full_attn_layers``):
  ``xing4.Xing4Attention`` with ``q_lora_rank`` None (``q = x W_q``)
  and no ``cos``/``sin`` (``mla_use_nope``: the 64 shared key dims and
  the query's are not rotated); materialised over a prompt, absorbed
  for one token a row, over a one-array latent page.
- **FFN**: layers below ``first_k_dense_replace`` ``xing4.Xing4MLP``
  of ``intermediate_size``; the others ``solar_open2.SolarOpen2MoE``
  (``xing4``'s float32 sigmoid router, top-k, renormalised weights
  times ``routed_scaling_factor``, dropless dispatch over the held
  share ``[experts_first, experts_first + experts_held)`` of
  ``num_experts``, one shared expert). The selection bias is zero and
  ``num_expert_group`` 1 limits nothing.
- **The stack and the seam** every decoder serves through:
  ``solar_open2.SolarOpen2Model`` / ``SolarOpen2ForCausalLM`` with this
  family's ``make_layer``.

What a token costs (``cache_layout``): an MLA layer one ``cache_dim``
vector, a KDA layer nothing. What a row keeps (``row_layout``): a KDA
layer its state and its convolution tail, an MLA layer nothing.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .. import nn
from .solar_open2 import (
    KDADims,
    SolarOpen2ForCausalLM,
    SolarOpen2KDA,
    SolarOpen2Model,
    SolarOpen2MoE,
)
from .xing4 import LatentCacheDims, Xing4Attention, Xing4MLP


def _linear_attn_default():
    full = (4, 8, 12, 16, 20, 24, 27)
    return {"full_attn_layers": list(full),
            "kda_layers": [i for i in range(1, 28) if i not in full],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig(LatentCacheDims, KDADims):
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the leading dense layer
    moe_intermediate_size: int = 1024      # one expert
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_scaling: dict | None = None
    linear_attn_config: dict = field(default_factory=_linear_attn_default)
    num_experts: int = 256                 # the router's width
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    # the share of the experts this program holds (None: all of them)
    experts_first: int = 0
    experts_held: int | None = None
    model_max_length: int = 1048576
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # not in the family's config: beta = sigmoid, without the factor 2
    kda_allow_neg_eigval: bool = False
    # tokens a step of the chunked scan takes
    kda_chunk: int = 64

    def __post_init__(self):
        if not self.mla_use_nope or self.rope_scaling:
            raise ValueError("KimiLinear: written for MLA without rope "
                             "(mla_use_nope true, rope_scaling null)")
        lin = self.linear_attn_config
        full = set(lin["full_attn_layers"])
        kda = lin.get("kda_layers")
        for number in range(1, self.num_hidden_layers + 1):
            if kda is not None and (number in full) == (number in kda):
                raise ValueError(
                    f"layer {number} is in both or in neither of "
                    f"full_attn_layers and kda_layers")
        last = self.experts_first + self.held
        if not 0 <= self.experts_first <= last <= self.num_experts:
            raise ValueError(
                f"experts [{self.experts_first}, {last}) are not among "
                f"{self.num_experts}")

    def is_mla(self, i):
        """Layer ``i`` (from 0) is latent attention: the published
        lists number the layers from 1."""
        return i + 1 in self.linear_attn_config["full_attn_layers"]

    def is_dense(self, i):
        return i < self.first_k_dense_replace

    # the names the shared expert layer reads its sizes under
    n_routed_experts = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    norm_topk_prob = property(lambda self: self.moe_renormalize)

    @property
    def held(self):
        """Experts this program holds of a layer's ``num_experts``."""
        return self.num_experts if self.experts_held is None \
            else int(self.experts_held)

    def cache_layout(self):
        """What a TOKEN costs a layer: one ``cache_dim`` vector in an
        MLA layer, nothing in a KDA layer."""
        return [((self.cache_dim,),) if self.is_mla(i) else ()
                for i in range(self.num_hidden_layers)]

    def row_layout(self):
        """What a ROW keeps a layer: a KDA layer its float32 state and
        its convolution tail (``kda_row_arrays``); an MLA layer
        nothing."""
        kept = self.kda_row_arrays()
        return [() if self.is_mla(i) else kept
                for i in range(self.num_hidden_layers)]

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            linear_attn_config={
                "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
                "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
            num_experts=16, num_experts_per_token=4, model_max_length=128,
            kda_chunk=8,
        )
        base.update(kw)
        return KimiLinearConfig(**base)


class KimiLinearDecoderLayer(nn.Layer):
    """(KDA | NoPE-MLA) x (dense | held-share experts)."""

    def __init__(self, cfg: KimiLinearConfig, i: int):
        super().__init__()
        self.mla = cfg.is_mla(i)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = Xing4Attention(cfg) if self.mla else SolarOpen2KDA(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = (Xing4MLP(cfg.hidden_size, cfg.intermediate_size)
                    if cfg.is_dense(i) else SolarOpen2MoE(cfg))

    def forward(self, x, cache=None, pos=None, page_table=None,
                length=None):
        h = self.input_layernorm(x)
        if self.mla:
            # the layer's one token array; no cos / sin: NoPE
            a, latent = self.mixer(
                h, cache=None if cache is None else cache[0], pos=pos,
                page_table=page_table)
            new_cache = None if cache is None else (latent,)
        else:
            a, new_cache = self.mixer(h, cache=cache, pos=pos,
                                      length=length)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


class KimiLinearModel(SolarOpen2Model):
    def make_layer(self, cfg, i):
        return KimiLinearDecoderLayer(cfg, i)


class KimiLinearForCausalLM(SolarOpen2ForCausalLM):
    """Served through the seam its base states: ``head_takes_row``,
    ``length`` for the row state, ``pop_step_counters`` over the expert
    layers."""

    model_class = KimiLinearModel
