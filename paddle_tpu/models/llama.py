"""Llama-family decoder (the flagship model for BASELINE config #4).

Reference parity: the Fleet hybrid-parallel Llama-2 path (BASELINE.json
"configs" #4; the model itself lives in PaddleNLP's llama modeling on top
of core ops — unverified, mount empty). TPU-first design:

- pre-norm RMSNorm -> fused Pallas kernel on TPU (kernels/rms_norm.py)
- rotary embeddings -> fused Pallas rope (kernels/rope.py) via
  incubate.nn.functional.fused_rotary_position_embedding
- causal attention -> flash attention (kernels/flash_attention.py) through
  F.scaled_dot_product_attention, with grouped-query attention (GQA);
  over a KV cache the GQA contraction is grouped, the cache read once
  per KV head (no repeated copy of K and V)
- SwiGLU MLP -> incubate.nn.functional.swiglu (one split gemm)
- everything shape-static and bf16-friendly so the whole step compiles
  onto the MXU as a handful of fused loops.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..nn.functional.attention import grouped_query_cache_attention
from ..incubate.nn import functional as IF
from ..quantization import kv as qkv


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int | None = None  # GQA; None -> MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_attention_heads

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=1000, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw):
        base = dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=32, num_attention_heads=32,
            max_position_embeddings=4096,
        )
        base.update(kw)
        return LlamaConfig(**base)


def causal_lm_loss(logits, labels, ignore_index=-100):
    """THE causal-LM training-loss seam: per-token CE (zeros at
    ``ignore_index`` rows); callers own the reduction. Routed by the
    active ``parallel.layout`` policy — when the installed mesh shards
    the vocab axis the loss goes through ParallelCrossEntropy (and, for
    ``vocab_parallel_loss`` policies, the explicit Megatron shard_map CE
    that never materializes the full-vocab fp32 logits block per chip);
    single-device and dp-only meshes take plain cross_entropy."""
    from ..parallel import layout as layout_mod
    from ..parallel import mesh as mesh_mod

    V = int(logits.shape[-1])
    flat = logits.reshape([-1, V])
    lab = labels.reshape([-1])
    pol = layout_mod.get_policy()
    deg = (
        mesh_mod.axis_size(pol.mp_axis) if mesh_mod.mesh_defined() else 1
    )
    if deg > 1:
        from ..distributed.fleet.meta_parallel import ParallelCrossEntropy

        return ParallelCrossEntropy(ignore_index=ignore_index)(flat, lab)
    return F.cross_entropy(
        flat, lab, reduction="none", ignore_index=ignore_index
    )


def _value(x):
    """The jax value of a Tensor, or ``x`` itself."""
    return x.value if hasattr(x, "value") else x


def _cache_attention(q, kk, vv, mask, attn_mask):
    """The attention every cache branch ends in: ``q`` ``[B, S, H, D]``
    over the dense cache view ``kk``/``vv`` ``[B, S_k, kvH, D]`` under
    the additive positional ``mask`` ``[B or 1, 1, S, S_k]``, combined
    with a user's ``attn_mask`` (e.g. left-padded prompts; it must
    broadcast over ``[B, H, S, S_k]``). Under GQA the contraction is
    grouped: each KV head is read once, never copied ``rep`` times; MHA
    takes ``F.scaled_dot_product_attention`` as it always did. Paged,
    slab-scalar and slab-per-row all come here because their token
    streams are pinned equal (paged == slab == ``generate``) and a
    grouped contraction is not bitwise the repeated one."""
    if attn_mask is not None:
        mask = mask + jnp.asarray(_value(attn_mask))
    if kk.shape[2] == q.shape[2]:
        return F.scaled_dot_product_attention(
            q, kk, vv, attn_mask=Tensor(mask), is_causal=False,
            training=False,
        )
    return grouped_query_cache_attention(q, kk, vv, Tensor(mask))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        h, d = config.hidden_size, config.head_dim
        self.q_proj = nn.Linear(h, config.num_attention_heads * d, bias_attr=False)
        self.k_proj = nn.Linear(h, config.kv_heads * d, bias_attr=False)
        self.v_proj = nn.Linear(h, config.kv_heads * d, bias_attr=False)
        self.o_proj = nn.Linear(config.num_attention_heads * d, h, bias_attr=False)

    def forward(self, x, rope_cos=None, rope_sin=None, attn_mask=None,
                cache=None, pos=None, page_table=None):
        """Training/eval path unchanged when ``cache is None``. With a
        ``cache=(k_cache, v_cache)`` pair ([B, S_max, kvH, D] jnp arrays)
        and a scalar ``pos`` (number of tokens already cached), the new
        keys/values are written at [pos, pos+S) and attention runs over
        the whole static cache with a position mask — the TPU decode
        pattern (static shapes, no growing tensors). Returns
        (out, new_cache) in cache mode.

        With ``page_table`` ([B, P] int32) the cache pair is a PAGE
        ARENA ([num_pages, page_size, kvH, D] x2) shared by every row:
        the step's k/v is scattered at each row's (page, offset) and
        attention runs over the table-gathered logical cache (S must be
        1 — the paged decode step). Page id 0 is the reserved garbage
        page. The addressing of all three modes is
        ``quantization.kv.write_and_view`` and, for the arena,
        ``write_and_attend_paged``, whose read is bounded by the
        batch's longest row.

        Under GQA every cache path contracts the query heads, grouped
        by their KV head, against the cache as it is stored
        (``_cache_attention``): K and V are never repeated to ``H``
        heads."""
        cfg = self.cfg
        B, S = int(x.shape[0]), int(x.shape[1])
        if page_table is not None and cache is None:
            raise ValueError("page_table requires a page-arena cache")
        q = self.q_proj(x).reshape([B, S, cfg.num_attention_heads, cfg.head_dim])
        k = self.k_proj(x).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([B, S, cfg.kv_heads, cfg.head_dim])
        # everything between the q/k/v and the o projections, whichever
        # of its paths runs, is one scope of the compiled program
        with jax.named_scope("attn_core"):
            out, new_cache = self._attn_core(
                q, k, v, rope_cos, rope_sin, attn_mask, cache, pos,
                page_table,
            )
        out = self.o_proj(out.reshape([B, S, -1]))
        return out if cache is None else (out, new_cache)

    def _attn_core(self, q, k, v, rope_cos, rope_sin, attn_mask, cache,
                   pos, page_table):
        """Rope, then the attention itself: without a cache GQA repeat
        + SDPA/flash; with one the cache write and view
        (``kv.write_and_view``: slab or per-row slab;
        ``kv.write_and_attend_paged``: page arena), the position mask
        and ``_cache_attention``, grouped under GQA.
        Returns ``(out [B, S, H, D], new_cache)``, ``new_cache`` None
        without a cache."""
        cfg = self.cfg
        S = int(q.shape[1])
        pos_ids = None
        if cache is not None:
            p = jnp.asarray(_value(pos))
            if p.ndim:  # per-row decode depths: gather rope rows by id
                pos_ids = p[:, None] + jnp.arange(S)[None, :]
        q, k, _ = IF.fused_rotary_position_embedding(
            q, k, None, sin=rope_sin, cos=rope_cos,
            position_ids=pos_ids, rotary_emb_base=cfg.rope_theta,
        )
        if cache is None:
            if cfg.kv_heads != cfg.num_attention_heads:
                rep = cfg.num_attention_heads // cfg.kv_heads
                k = k.repeat_interleave(rep, axis=2)
                v = v.repeat_interleave(rep, axis=2)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                training=self.training,
            )
            return out, None
        # every cache mode ends in the SAME _cache_attention, so paged,
        # slab and net.generate token streams stay bit-identical (masked
        # columns contribute exact zeros); int8 caches come back
        # dequantized to the compute dtype, plain ones as stored (the
        # attention upcasts at the matmul)
        def attend(views, mask):
            kk, vv = views
            return _cache_attention(q, Tensor(kk), Tensor(vv), mask,
                                    attn_mask)

        fresh = (k.value, v.value)
        if page_table is None:
            cache, views, cols = qkv.write_and_view(
                cache, fresh, p, q.value.dtype)
            return attend(views, qkv.position_mask(
                cols, views[0].shape[1])), cache
        # the read stops at the batch's longest row (the span ladder
        # of kv.write_and_attend_paged)
        cache, out = qkv.write_and_attend_paged(
            cache, fresh, p, jnp.asarray(_value(page_table)),
            lambda views, mask: attend(views, mask).value, q.value.dtype)
        return Tensor(out), cache


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        # gate+up as ONE gemm; swiglu splits (llama fused-gate pattern)
        self.gate_up_proj = nn.Linear(h, 2 * ffn, bias_attr=False)
        self.down_proj = nn.Linear(ffn, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(IF.swiglu(self.gate_up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope_cos=None, rope_sin=None, attn_mask=None,
                cache=None, pos=None, page_table=None):
        if cache is not None:
            a, new_cache = self.self_attn(
                self.input_layernorm(x), rope_cos, rope_sin, attn_mask,
                cache=cache, pos=pos, page_table=page_table,
            )
            h = x + a
            return h + self.mlp(self.post_attention_layernorm(h)), new_cache
        h = x + self.self_attn(
            self.input_layernorm(x), rope_cos, rope_sin, attn_mask
        )
        return h + self.mlp(self.post_attention_layernorm(h))


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None,
                apply_final_norm=True, page_table=None, exit_layer=None):
        """``caches``: list of per-layer (k_cache, v_cache) for decode
        (returns (hidden, new_caches)); None for the training path.
        With ``page_table`` the caches are per-layer page arenas
        ([num_pages, page_size, kvH, D] x2) and decode attention runs
        through the table (serving's paged KV pool).
        ``apply_final_norm=False`` returns the pre-norm hidden state
        (a caller that wants the caches alone skips the norm:
        ``serving/speculative.py``'s chunk-write pass).
        ``exit_layer=N`` runs only the first N decoder layers (the
        self-speculative draft seam: the truncated stack + the shared
        head IS the draft model — ``caches`` then carries N entries)."""
        cfg = self.config
        S = int(input_ids.shape[1])
        layers = (self.layers if exit_layer is None
                  else list(self.layers)[:int(exit_layer)])
        from ..kernels.rope import build_rope_cache

        if caches is not None:
            if page_table is not None:
                # logical capacity: the rope table must cover every
                # addressable position, pages * page_size
                S_max = (int(page_table.shape[1])
                         * int(caches[0][0].shape[1]))
            else:
                S_max = caches[0][0].shape[1]
            cos, sin = build_rope_cache(
                S_max, cfg.head_dim, base=cfg.rope_theta
            )
            p = jnp.asarray(_value(pos))
            if p.ndim == 0:
                # rope rows for the tokens being fed: [p, p+S)
                cos = jax.lax.dynamic_slice_in_dim(cos, p, S, axis=1)
                sin = jax.lax.dynamic_slice_in_dim(sin, p, S, axis=1)
            # else: per-row positions — pass the full tables; attention
            # gathers each row's slice via rope position_ids
            cos_t, sin_t = Tensor(cos), Tensor(sin)
            h = self.embed_tokens(input_ids)
            new_caches = []
            for layer, cache in zip(layers, caches):
                h, c2 = layer(h, cos_t, sin_t, attn_mask,
                              cache=cache, pos=pos,
                              page_table=page_table)
                new_caches.append(c2)
            return (self.norm(h) if apply_final_norm else h), new_caches
        cos, sin = build_rope_cache(S, cfg.head_dim, base=cfg.rope_theta)
        cos_t, sin_t = Tensor(cos), Tensor(sin)
        h = self.embed_tokens(input_ids)
        for layer in layers:
            h = layer(h, cos_t, sin_t, attn_mask)
        return self.norm(h) if apply_final_norm else h


class LlamaFlopsMixin:
    """Shared param/FLOPs accounting for every Llama head (single-device
    and pipe): 6*N + attention quadratic term (12*L*H*S per token with
    H=hidden — standard PaLM-appendix accounting). Single home so the
    bench's MFU math cannot drift between model variants."""

    def num_params(self):
        return sum(int(p.size) for p in self.parameters())

    def flops_per_token(self, seq_len):
        cfg = self.config
        return (
            6 * self.num_params()
            + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
        )


class LlamaForCausalLM(LlamaFlopsMixin, nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size, bias_attr=False
            )

    def _head(self, h):
        """Hidden state to logits, always under scope ``lm_head``: the
        ``lm_head`` layer opens it itself; the tied head (the
        embedding's transpose) is no layer."""
        if self.lm_head is not None:
            return self.lm_head(h)
        with jax.named_scope("lm_head"):
            return F.linear(h, self.model.embed_tokens.weight.t())

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None,
                page_table=None, exit_layer=None):
        if caches is not None:
            h, new_caches = self.model(
                input_ids, attn_mask, caches=caches, pos=pos,
                page_table=page_table, exit_layer=exit_layer,
            )
            return self._head(h), new_caches
        h = self.model(input_ids, attn_mask, exit_layer=exit_layer)
        return self._head(h)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=0, num_beams=1, cache_dtype=None):
        from .generation import DEFAULT_CACHE_DTYPE
        from .generation import generate as _generate

        return _generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p, num_beams=num_beams,
            eos_token_id=eos_token_id, seed=seed,
            cache_dtype=cache_dtype or DEFAULT_CACHE_DTYPE,
        )

