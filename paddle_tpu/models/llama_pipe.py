"""Llama under Fleet hybrid parallel — the BASELINE config #4 path.

Reference parity: PaddleNLP's ``LlamaForCausalLMPipe`` (a PipelineLayer
of TP decoder blocks driven by fleet's PipelineParallel — unverified,
mount empty). TPU-first design: the same [prefix | uniform TP blocks |
suffix] structure, but executed as ONE jitted SPMD program — Megatron TP
via GSPMD shardings (mp axis), the microbatch schedule via the compiled
ppermute ring (pp axis), data parallel via batch sharding (dp axis).

The sharding layout comes from the ACTIVE ``parallel.layout``
LayoutPolicy (swap it with ``layout.use_policy(...)`` — no model edits);
under the default ``tp-pp-dp`` policy, per decoder block (mesh axes
(dp, pp, mp)):
- q/k/v projections: ColumnParallelLinear, weight P(None, 'mp') — heads
  split across mp ranks;
- o_proj: RowParallelLinear, weight P('mp', None) — the attention
  output's head dim is contracted locally, XLA inserts the mp allreduce;
- gate/up projections: ColumnParallelLinear (SwiGLU operands stay
  mp-sharded, multiplied elementwise shard-local);
- down_proj: RowParallelLinear;
- RMSNorm weights: replicated (tiny);
- embedding: VocabParallelEmbedding, weight P('mp', None) (vocab rows);
- lm head: ColumnParallelLinear gather_output=False + the distributed
  softmax of ParallelCrossEntropy over vocab-sharded logits (the
  explicit Megatron shard_map CE under ``vocab_parallel_loss``
  policies — the fp32 logits block stays [rows, V/mp] per chip).

``use_sep_attention`` policies additionally route decoder attention
through the sep-axis ring (parallel.ring_flash_attention) whenever the
mesh carries sep degree > 1 — the long-context (S=8192) regime.

Each block rebuilds its rope cache from the static sequence length —
XLA constant-folds it once per compilation; blocks carry no buffers (a
requirement of the compiled pipeline's stacked-scan schedule).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..incubate.nn import functional as IF
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    LayerDesc,
    PipelineLayer,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..parallel import layout as layout_mod
from ..parallel import mesh as mesh_mod
from ..parallel.sep_ops import ring_flash_attention
from .llama import LlamaConfig, LlamaFlopsMixin, causal_lm_loss


class LlamaDecoderLayerTP(nn.Layer):
    """One uniform pipeline block: TP attention + TP SwiGLU MLP."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        h, d = config.hidden_size, config.head_dim
        self.input_layernorm = nn.RMSNorm(h, epsilon=config.rms_norm_eps)
        self.q_proj = ColumnParallelLinear(
            h, config.num_attention_heads * d, has_bias=False,
            gather_output=False,
        )
        self.k_proj = ColumnParallelLinear(
            h, config.kv_heads * d, has_bias=False, gather_output=False
        )
        self.v_proj = ColumnParallelLinear(
            h, config.kv_heads * d, has_bias=False, gather_output=False
        )
        self.o_proj = RowParallelLinear(
            config.num_attention_heads * d, h, has_bias=False,
            input_is_parallel=True,
        )
        self.post_attention_layernorm = nn.RMSNorm(
            h, epsilon=config.rms_norm_eps
        )
        ffn = config.intermediate_size
        self.gate_proj = ColumnParallelLinear(
            h, ffn, has_bias=False, gather_output=False
        )
        self.up_proj = ColumnParallelLinear(
            h, ffn, has_bias=False, gather_output=False
        )
        self.down_proj = RowParallelLinear(
            ffn, h, has_bias=False, input_is_parallel=True
        )

    def forward(self, x):
        cfg = self.cfg
        B, S = int(x.shape[0]), int(x.shape[1])
        from ..kernels.rope import build_rope_cache

        cos, sin = build_rope_cache(S, cfg.head_dim, base=cfg.rope_theta)
        h = self.input_layernorm(x)
        q = self.q_proj(h).reshape(
            [B, S, cfg.num_attention_heads, cfg.head_dim]
        )
        k = self.k_proj(h).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        v = self.v_proj(h).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        with jax.named_scope("attn_core"):  # as LlamaAttention's
            a = self._attn_core(q, k, v, cos, sin)
        x = x + self.o_proj(a.reshape([B, S, -1]))
        h2 = self.post_attention_layernorm(x)
        return x + self.down_proj(
            IF.swiglu(self.gate_proj(h2), self.up_proj(h2))
        )


    def _attn_core(self, q, k, v, cos, sin):
        cfg = self.cfg
        q, k, _ = IF.fused_rotary_position_embedding(
            q, k, None, sin=Tensor(sin), cos=Tensor(cos),
            rotary_emb_base=cfg.rope_theta,
        )
        if cfg.kv_heads != cfg.num_attention_heads:
            rep = cfg.num_attention_heads // cfg.kv_heads
            k = k.repeat_interleave(rep, axis=2)
            v = v.repeat_interleave(rep, axis=2)
        pol = layout_mod.get_policy()
        if (
            pol.use_sep_attention
            and mesh_mod.mesh_defined()  # never install a mesh as a side effect
            and mesh_mod.axis_size(pol.sep_axis) > 1
        ):
            # long-context policies: exact full attention over the
            # sep-sharded sequence via the KV rotation ring — per-device
            # score memory stays O((S/sep)^2) per hop
            return ring_flash_attention(q, k, v, causal=True,
                                        axis=pol.sep_axis)
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=self.training
        )


class _FinalNorm(nn.RMSNorm):
    pass  # distinct type so the block-run detector keeps it in the suffix


class _LMHead(ColumnParallelLinear):
    """The vocab-parallel head. A pipeline layer is registered under
    its index, so the head opens the scope ``lm_head`` itself, as
    ``LlamaForCausalLM``'s does through its attribute name."""

    def forward(self, x):
        with jax.named_scope("lm_head"):
            return super().forward(x)


class LlamaForCausalLMPipe(LlamaFlopsMixin, PipelineLayer):
    """PipelineLayer over TP Llama decoder blocks with the vocab-parallel
    embedding prefix and the TP head + distributed-softmax loss suffix.

    ``num_stages`` defaults to the hybrid mesh's pp degree. Train it with
    ``fleet.distributed_model`` / ``PipelineParallel.train_batch``
    (pipeline_configs={'compiled': True} for the single-program path) —
    exactly the reference's Fleet hybrid flow for BASELINE config #4.
    """

    def __init__(self, config: LlamaConfig, num_stages=None,
                 num_virtual_pipeline_stages=1, recompute_interval=0,
                 topology=None):
        from ..parallel import mesh as mesh_mod

        if num_stages is None:
            num_stages = mesh_mod.global_mesh_shape().get("pp", 1)
        self.config = config

        def loss_fn(logits, labels):
            # one seam for every causal-LM loss: routes through the
            # active layout policy (vocab-parallel CE when enabled)
            return causal_lm_loss(logits, labels).mean()

        super().__init__(
            [LayerDesc(VocabParallelEmbedding, config.vocab_size,
                       config.hidden_size)]
            + [LayerDesc(LlamaDecoderLayerTP, config)
               for _ in range(config.num_hidden_layers)]
            + [
                LayerDesc(_FinalNorm, config.hidden_size,
                          epsilon=config.rms_norm_eps),
                LayerDesc(_LMHead, config.hidden_size,
                          config.vocab_size, has_bias=False,
                          gather_output=False),
            ],
            num_stages=num_stages,
            loss_fn=loss_fn,
            num_virtual_pipeline_stages=num_virtual_pipeline_stages,
            recompute_interval=recompute_interval,
            topology=topology,
        )

    # ------------------------------------------------- serving bridge
    def to_causal_lm(self):
        """Convert to a :class:`LlamaForCausalLM` carrying these weights
        — the train-hybrid -> serve path: a pipe-trained checkpoint
        decodes through ``generate()`` / exports via ``GreedyDecoder``.

        Under GSPMD parameter values are GLOBAL logical arrays (the mesh
        placement is just layout), so the mapping is pure renaming plus
        one concat: the pipe keeps gate/up as separate TP columns while
        the single model fuses them into ``gate_up_proj`` (swiglu splits
        the fused output in half, so ``concat(gate, up)`` on the out dim
        is exact).
        """
        from .llama import LlamaForCausalLM
        from ..core.lazy import LazyGuard

        cfg = self.config
        if cfg.tie_word_embeddings:
            # the pipe ALWAYS trains a separate head (its suffix
            # ColumnParallelLinear); a tied LlamaForCausalLM has
            # lm_head=None and serves embed_tokens.T — the trained head
            # would be silently dropped and every logit wrong
            raise ValueError(
                "to_causal_lm: config.tie_word_embeddings=True cannot "
                "be converted — LlamaForCausalLMPipe trains an untied "
                "LM head (pipeline suffix), but the tied "
                "LlamaForCausalLM would discard it and serve "
                "embed_tokens.T logits. Train the pipe with an untied "
                "config, or copy the weights into a model whose head "
                "layout matches."
            )
        L = cfg.num_hidden_layers
        src = {k: p.value for k, p in self.named_parameters()}
        state = {
            "model.embed_tokens.weight": src["0.weight"],
            "model.norm.weight": src[f"{L + 1}.weight"],
            "lm_head.weight": src[f"{L + 2}.weight"],
        }
        for i in range(L):
            b, t = f"{i + 1}.", f"model.layers.{i}."
            for name in ("input_layernorm.weight",
                         "post_attention_layernorm.weight"):
                state[t + name] = src[b + name]
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                state[t + f"self_attn.{name}.weight"] = src[
                    b + f"{name}.weight"
                ]
            state[t + "mlp.gate_up_proj.weight"] = jnp.concatenate(
                [src[b + "gate_proj.weight"], src[b + "up_proj.weight"]],
                axis=1,
            )
            state[t + "mlp.down_proj.weight"] = src[b + "down_proj.weight"]
        with LazyGuard():  # no wasted init: every param is overwritten
            net = LlamaForCausalLM(cfg)
        for k, p in net.named_parameters():
            if k not in state:
                raise KeyError(
                    f"pipe->single conversion missing parameter {k!r}"
                )
            p.value = state[k]
        net.eval()
        return net
