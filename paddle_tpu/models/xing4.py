"""Xing4.0 decoder: latent attention, sigmoid-routed experts run
dropless, and residual streams mixed by manifold-constrained
hyper-connections (mHC).

Written from the public ``config.json`` of ``Xing4.0-29B-A4B``
(``model_type`` ``xing4_0``); its keys are DeepSeek-V3's for the
attention, the experts and the multi-token-prediction module, and
arXiv 2512.24880's for the residual path. ``x`` below is ``[T, n, C]``:
``n = hc_mult`` residual streams of width ``C``.

- **Streams.** The embedding is replicated over the ``n`` streams; the
  output is ``final_norm(sum over streams) @ head``.
- **mHC**, around the attention and around the FFN of every layer, each
  with its own ``phi``, ``bias``, ``alpha``::

      xf    = RMSNorm(vec(X))                     # no weight, eps hc_eps
      Hpre  = sigmoid(a_pre * (xf phi_pre) + b_pre)           # [n]
      Hpost = 2 sigmoid(a_post * (xf phi_post) + b_post)      # [n]
      Hres  = Sinkhorn(clip(a_res * mat(xf phi_res) + b_res)) # [n, n]
      X     = Hres X + Hpost[:, None] * F(RMSNorm_w(Hpre X))

  Sinkhorn-Knopp: ``M = exp(.)``, then ``hc_sinkhorn_iters`` times
  rows then columns divided by their sums ``+ hc_eps``. The maps are
  computed in float32, tokens along the lanes.
- **MLA.** ``cq = RMSNorm_w(h Wqa)``, ``[q_nope | q_rope] = cq Wqb``;
  ``[ckv | k_rope] = h Wkva``, ``ckv = RMSNorm_w(ckv)``; ``[k_nope | v]
  = ckv Wkvb``; YaRN rope on ``q_rope`` and the one ``k_rope`` all
  heads share; softmax scale ``(dn + dr)^-0.5 * mscale^2``. The cache
  is ONE array a layer, ``[ckv | k_rope]`` a token, zero-padded to
  whole lanes (``Xing4Config.cache_layout``, ``cache_dim``). Prefill
  materialises K and V from the latent; a one-token step runs
  ABSORBED: ``q_nope`` is taken through ``Wkvb``'s key half into the
  latent space, scores and the value sum run against the cached latent
  itself, and ``Wkvb``'s value half is applied to the 512-wide result.
- **Experts.** ``s = sigmoid(h Wg)`` in float32, top-k of ``s +
  e_bias``, weights ``s[chosen] / sum * routed_scaling_factor``, plus
  a shared expert. Dispatch is dropless and static-shaped: the ``T x
  k`` assignments sorted by expert, group sizes from a bincount, one
  ``jax.lax.ragged_dot`` a projection. No capacity, no dropped token.
- **MTP** (``mtp_logits``): ``[RMSNorm_w(h_main_i) ; RMSNorm_w(Emb(
  t_{i+1}))] W_eh``, one expert block, the shared embedding and head.
  Not used when serving (``num_nextn_predict_layers`` 0 builds none).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core import dispatch
from ..core.tensor import Tensor
from ..incubate.nn import functional as IF
from ..kernels.flash_attention import flash_attention_fwd
from ..nn import initializer as I
from ..quantization import kv as qkv

_F32 = jnp.float32


def _yarn_default():
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


class LatentCacheDims:
    """What a config with MLA's keys (``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``rope_scaling``) derives from them: the sizes
    of a cached token and the softmax scale. Every family whose layers
    run :func:`mla_core` states them through this."""

    @property
    def latent_dim(self):
        """Numbers a cached token is: the normed latent and the key
        dims every head shares (roped where the family ropes them)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_dim(self):
        """``latent_dim`` as it is stored: zero-padded to whole lanes
        of 128. A ``[pages, 16, 576]`` array is no whole number of the
        chip's tiles; its default device layout then puts the PAGE axis
        minor, and every decode step copies each layer's arena into a
        row-major one and back (seen in the program compiled for the
        chip). ``[pages, 16, 640]`` is row-major as it lies."""
        return 128 * -(-self.latent_dim // 128)

    @property
    def softmax_scale(self):
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return qk ** -0.5 * yarn_mscale(self.rope_scaling) ** 2


@dataclass
class Xing4Config(LatentCacheDims):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216          # the leading dense layers
    moe_intermediate_size: int = 1024      # one expert
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict | None = field(default_factory=_yarn_default)
    tie_word_embeddings: bool = False

    def cache_layout(self):
        """One array a layer, one ``cache_dim`` vector a token."""
        return [((self.cache_dim,),)] * self.num_hidden_layers

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2, hc_mult=4,
            max_position_embeddings=128,
            rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32},
        )
        base.update(kw)
        return Xing4Config(**base)


# ------------------------------------------------------------------ rope
def yarn_mscale(scaling):
    """The attention-scale factor ``m`` of YaRN (``0.1 * mscale_all_dim
    * ln(factor) + 1``); cos and sin stay unscaled because ``mscale``
    equals ``mscale_all_dim`` in this family."""
    if not scaling or scaling.get("factor", 1) <= 1:
        return 1.0
    return 0.1 * float(scaling.get("mscale_all_dim", 0) or 0) \
        * math.log(float(scaling["factor"])) + 1.0


def yarn_inv_freq(dim, base, scaling):
    """Rope frequencies ``[dim / 2]``: plain below ``beta_fast``
    rotations over the original context, divided by ``factor`` above
    ``beta_slow``, a linear ramp between (DeepSeek-V3's YaRN)."""
    exps = jnp.arange(0, dim, 2, dtype=_F32) / dim
    plain = 1.0 / base ** exps
    if not scaling or scaling.get("factor", 1) <= 1:
        return plain
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / float(scaling["factor"]) * ramp + plain * (1.0 - ramp)


def _rope(x, cos, sin):
    """Rotate-half on the last axis of ``x``; ``cos``/``sin`` broadcast
    against ``x[..., : d / 2]``. Computed in float32."""
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d].astype(_F32), x[..., d:].astype(_F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


# ------------------------------------------------------------- attention
def _softmax_attend(scores, mask, scale, dtype):
    s = scores * scale
    if mask is not None:
        s = s + mask
    return jax.nn.softmax(s.astype(_F32), axis=-1).astype(dtype)


def mla_materialised(q_nope, q_rope, view, w_kvb, mask, scale):
    """Attention with K and V made from the latent: ``q_*`` ``[B, S, H,
    .]`` against ``view`` ``[B, S_k, latent_dim]`` (or ``cache_dim``:
    what lies past the rope dims is padding); ``mask`` additive
    ``[B or 1, 1, S, S_k]`` or None for plain causal over ``S == S_k``
    fresh tokens. Returns ``[B, S, H, dv]``."""
    b, sk = view.shape[:2]
    h, dn = q_nope.shape[2], q_nope.shape[3]
    kvl = w_kvb.shape[0]
    kv = jnp.einsum("bkc,cm->bkm", view[..., :kvl].astype(w_kvb.dtype),
                    w_kvb).reshape(b, sk, h, -1)
    dr = q_rope.shape[-1]
    k_rope = jnp.broadcast_to(
        view[:, :, None, kvl:kvl + dr].astype(kv.dtype), (b, sk, h, dr))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([kv[..., :dn], k_rope], -1)
    v = kv[..., dn:]
    if mask is None:
        return _causal_attention(q, k, v, scale)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=_F32)
    p = _softmax_attend(s, mask, scale, v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _causal_attention(q, k, v, scale):
    """Causal self-attention of ``S`` fresh tokens, q/k ``[B, S, H,
    dn + dr]`` and v ``[B, S, H, dv]``. From the length at which the
    flash kernel is wanted, the head dims are zero-padded to one width
    it takes (exact: zeros add nothing to a score, and the padded value
    columns are cut off), so the scores never lie in HBM."""
    dqk, dv = q.shape[-1], v.shape[-1]
    if q.shape[1] >= 2048:
        width = 128 * -(-max(dqk, dv) // 128)
        width = width if width in (128, 256) else 256 * -(-width // 256)
        pad = lambda a: jnp.pad(
            a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))
        return flash_attention_fwd(pad(q), pad(k), pad(v), causal=True,
                                   scale=scale)[..., :dv]
    sq = q.shape[1]
    causal = jnp.where(jnp.tril(jnp.ones((sq, sq), bool)), 0.0, -jnp.inf)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=_F32)
    p = _softmax_attend(s, causal[None, None], scale, v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def mla_absorbed(q_nope, q_rope, view, w_kvb, mask, scale):
    """The same attention with ``Wkvb`` absorbed: the query goes into
    the latent space (``dn -> kv_lora_rank`` a head), scores and the
    value sum run against ``view`` ``[B, S_k, latent_dim]`` as it is
    cached, and ``Wkvb``'s value half maps the result to ``dv``. Reads
    the cache once a head-batch and makes no K or V."""
    h, dn = q_nope.shape[2], q_nope.shape[3]
    kvl = w_kvb.shape[0]
    w = w_kvb.reshape(kvl, h, -1)
    q_abs = jnp.einsum("bqhd,chd->bqhc", q_nope, w[..., :dn])
    q_cat = jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], -1)
    # a cached view is zero-padded to whole lanes: so is the query
    q_cat = jnp.pad(q_cat, ((0, 0),) * 3
                    + ((0, view.shape[-1] - q_cat.shape[-1]),))
    view = view.astype(q_cat.dtype)
    s = jnp.einsum("bqhc,bkc->bhqk", q_cat, view,
                   preferred_element_type=_F32)
    p = _softmax_attend(s, mask, scale, view.dtype)
    o_lat = jnp.einsum("bhqk,bkc->bqhc", p, view)[..., :kvl]
    return jnp.einsum("bqhc,chd->bqhd", o_lat, w[..., dn:])


def mla_core(q, ckv, k_rope, w_kvb, cos, sin, *, cfg, cache=None, pos=None,
             page_table=None, absorbed=None):
    """Rope, the latent write, the page gather and the attention: ``q``
    ``[B, S, H, dn + dr]``, ``ckv`` ``[B, S, kv_lora_rank]`` (normed),
    ``k_rope`` ``[B, S, dr]``; ``cos``/``sin`` ``[B or 1, S, dr / 2]``
    at the tokens' positions, or both None for a family that does not
    rotate (NoPE: the query's and the key's ``dr`` dims go through as
    they are). ``cache`` is the layer's one array: a
    block or slab ``[B, S_max, cache_dim]`` (``pos`` scalar or
    ``[B]``) or, with ``page_table`` ``[B, P]``, a page arena
    ``[pages, page_size, cache_dim]``. One token a row runs absorbed,
    more materialised (``absorbed`` overrides). Returns ``(out [B, S,
    H, dv], new_cache)``."""
    dn = cfg.qk_nope_head_dim
    s = q.shape[1]
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    if cos is not None:
        q_rope = _rope(q_rope, cos[:, :, None], sin[:, :, None])
        k_rope = _rope(k_rope, cos, sin)
    latent = jnp.concatenate([ckv, k_rope], -1)
    scale = cfg.softmax_scale
    if absorbed is None:
        absorbed = s == 1
    attend = mla_absorbed if absorbed else mla_materialised
    if cache is None:
        mask = None
        if absorbed:
            mask = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                             -jnp.inf)[None, None]
        return attend(q_nope, q_rope, latent, w_kvb, mask, scale), None
    fresh = latent
    latent = jnp.pad(latent.astype(cache.dtype), (
        (0, 0), (0, 0), (0, cache.shape[-1] - latent.shape[-1])))
    p = jnp.asarray(pos)
    if page_table is not None:
        # the read stops at the batch's longest row (the span ladder
        # of kv.write_and_attend_paged)
        (cache,), out = qkv.write_and_attend_paged(
            (cache,), (latent,), p, page_table,
            lambda views, mask: attend(q_nope, q_rope, views[0], w_kvb,
                                       mask, scale))
        return out, cache
    (cache,), (view,), cols = qkv.write_and_view((cache,), (latent,), p)
    if p.ndim == 0 and s == cache.shape[1] and not absorbed:
        # a chunk as long as its block can only start at 0 (the
        # engines' prefill programs): plain causal attention among
        # the fresh tokens, no mask over the block
        return attend(q_nope, q_rope, fresh, w_kvb, None, scale), cache
    mask = qkv.position_mask(cols, view.shape[1])
    return attend(q_nope, q_rope, view, w_kvb, mask, scale), cache


class Xing4Attention(nn.Layer):
    """MLA over any config that states its keys (``LatentCacheDims``).
    ``q_lora_rank`` None: the query comes from one projection,
    uncompressed and unnormed."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        c, h = cfg.hidden_size, cfg.num_attention_heads
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        if cfg.q_lora_rank:
            self.q_a_proj = lin(c, cfg.q_lora_rank)
            self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                            cfg.rms_norm_eps)
            self.q_b_proj = lin(cfg.q_lora_rank, h * dq)
        else:
            self.q_proj = lin(c, h * dq)
        self.kv_a_proj = lin(c, cfg.latent_dim)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = lin(cfg.kv_lora_rank,
                             h * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = lin(h * cfg.v_head_dim, c)

    def forward(self, x, cos=None, sin=None, cache=None, pos=None,
                page_table=None):
        """``x`` ``[B, S, C]``; ``cos``/``sin`` None: no rotation.
        Returns ``(out, new_cache)``, the cache None without one."""
        cfg = self.cfg
        b, s = int(x.shape[0]), int(x.shape[1])
        q = (self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
             if cfg.q_lora_rank else self.q_proj(x))
        q = q.reshape([b, s, cfg.num_attention_heads, -1])
        kv = self.kv_a_proj(x)
        ckv = self.kv_a_layernorm(kv[..., :cfg.kv_lora_rank])
        k_rope = kv[..., cfg.kv_lora_rank:]

        def core(qv, cv, kv_, wv):
            return mla_core(qv, cv, kv_, wv, cos, sin, cfg=cfg, cache=cache,
                            pos=pos, page_table=page_table)

        # everything between the projections, whichever path runs, is
        # one scope of the compiled program (the name Llama uses)
        with jax.named_scope("attn_core"):
            if cache is None:
                out = dispatch.apply(
                    "mla_attention", lambda *a: core(*a)[0],
                    (q, ckv, k_rope, self.kv_b_proj.weight), cache=False)
                new_cache = None
            else:
                out, new_cache = core(q.value, ckv.value, k_rope.value,
                                      self.kv_b_proj.weight.value)
                out = Tensor(out)
        return self.o_proj(out.reshape([b, s, -1])), new_cache


# --------------------------------------------------------------- experts
def moe_scores(h, w_gate):
    """Router scores ``[T, E]``: ``sigmoid(h Wg)`` in float32, as the
    published code runs it."""
    return jax.nn.sigmoid(jnp.dot(
        h.astype(_F32), w_gate.astype(_F32),
        precision=jax.lax.Precision.HIGHEST))


def moe_choose(scores, e_bias, top_k):
    """The ``top_k`` experts a token goes to ``[T, k]``: chosen by
    ``score + e_bias``; the bias takes no part in the weights."""
    return jax.lax.top_k(scores + e_bias.astype(_F32), top_k)[1]


def moe_weights(scores, idx, *, scale, renorm):
    """Weights ``[T, k]`` of the chosen experts: their scores, made to
    sum to one, times ``routed_scaling_factor``."""
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * scale


# Below this many sorted rows a narrower grouped matmul gains nothing on
# the chip: at 128 rows the kernel already runs at the speed its
# experts' weights stream (PERF.md section 6, PR 33).
_ROW_FLOOR = 128


def row_ladder(rows):
    """The sorted-row counts the dispatch of a held SHARE may be bounded
    to, ascending: a quarter and a half of the ``rows`` = ``T k``
    assignments, rounded up, and all of them; no rung under
    ``_ROW_FLOOR`` rows unless it is the last. 512 rows: 128, 256, 512;
    128 rows or fewer: the one rung. A function of the row count alone:
    the dispatch, the ``dispatch_rows`` counter and the tests all take
    it from here."""
    return tuple(sorted({max(-(-rows // part), min(rows, _ROW_FLOOR))
                         for part in (4, 2, 1)}))


def row_rung(rows, n_local):
    """Index into :func:`row_ladder` of the narrowest rung that holds
    ``n_local`` rows (a traced scalar in the program, a number in the
    tests). The last rung is every row, so any count is held."""
    return (n_local > np.asarray(row_ladder(rows)[:-1])).sum()


def dispatch_rows(rows, n_local):
    """The rows :func:`moe_dispatch` hands its grouped matmuls when
    ``n_local`` of its ``rows`` assignments land on held experts."""
    return jnp.asarray(row_ladder(rows), jnp.int32)[row_rung(rows, n_local)]


def moe_dispatch(h, idx, w, w_gate_up, w_down, first=0, held=None):
    """Dropless expert FFN: ``h`` ``[T, C]``, assignments ``idx``/``w``
    ``[T, k]``, experts stacked ``[E, C, 2 I]`` / ``[E, I, C]``. The
    ``T k`` assignments are sorted by expert, each projection is one
    grouped matmul over the sorted rows (group sizes from a bincount),
    and every row comes back to its token: no capacity, none dropped.

    With ``held`` the stacks are a SHARE of the experts ``idx`` numbers:
    experts ``[first, first + held)``. An assignment to an absent
    expert is sorted behind the held groups and belongs to no group, so
    the ``n_local`` rows that do lie in a group are sorted rows ``[0,
    n_local)``. Of :func:`row_ladder` the program picks, on the device
    from ``n_local``, the narrowest rung ``R`` that holds them, and one
    ``jax.lax.switch`` runs that rung's branch: gather sorted rows
    ``[0, R)``, both grouped matmuls and SwiGLU over ``R`` rows, and
    each assignment's row back to its token. The last rung is all ``T
    k`` rows, so nothing is ever dropped and the result does not depend
    on the rung: it is the held experts' part of the sum, under weights
    made over all ``k``. (The chip's grouped matmul multiplies a whole
    tile of ``min(rows, 512)`` rows for every expert that got a row:
    handed 512 rows of which 64 lie in groups it is compute-bound on
    rows of no group.)"""
    t, k = idx.shape
    n_exp, _, two_i = w_gate_up.shape
    flat = idx.reshape(-1)
    if held is not None:
        local = flat - first
        here = (local >= 0) & (local < held)
        flat = jnp.where(here, local, held)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n_exp).astype(jnp.int32)

    def experts(rows, h, order, sizes, w_gate_up, w_down):
        """Sorted rows ``[0, rows)`` through the experts ``sizes``
        groups them under, each assignment's row back at its place
        ``[T k, C]`` (an assignment sorted past ``rows`` reads the last
        row: it lies in no group and is zeroed below)."""
        # all T k rows: the operations the dispatch always traced
        whole = rows == t * k
        xs = h[(order if whole else order[:rows]) // k]
        gu = jax.lax.ragged_dot(xs, w_gate_up, sizes)
        act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
        ys = jax.lax.ragged_dot(act, w_down, sizes)
        inv = jnp.argsort(order)
        return ys[inv if whole else jnp.minimum(inv, rows - 1)]

    if held is None:
        back = experts(t * k, h, order, sizes, w_gate_up, w_down)
    else:
        back = jax.lax.switch(
            row_rung(t * k, jnp.sum(here)),
            [functools.partial(experts, rows) for rows in row_ladder(t * k)],
            h, order, sizes, w_gate_up, w_down)
        # an absent assignment's row is a row of no group, or another
        # assignment's: it adds nothing
        back = jnp.where(here[:, None], back, 0)
    return jnp.sum(back.reshape(t, k, -1).astype(_F32) * w[..., None],
                   axis=1).astype(h.dtype)


def experts_touched(idx, n_exp):
    """Experts that got at least one of the assignments ``idx``."""
    return jnp.sum(jnp.bincount(idx.reshape(-1), length=n_exp) > 0
                   ).astype(jnp.int32)


class Xing4MLP(nn.Layer):
    """SwiGLU, gate and up as one gemm (the dense layers, and the
    shared expert)."""

    def __init__(self, hidden, ffn):
        super().__init__()
        self.gate_up_proj = nn.Linear(hidden, 2 * ffn, bias_attr=False)
        self.down_proj = nn.Linear(ffn, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(IF.swiglu(self.gate_up_proj(x)))


class Xing4MoE(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.cfg = cfg
        c, e, i = (cfg.hidden_size, cfg.n_routed_experts,
                   cfg.moe_intermediate_size)
        init = I.Normal(0.0, 0.02)
        self.gate_weight = self.create_parameter(
            [c, e], default_initializer=init)
        # the selection bias: trained without gradient where this
        # family is trained; here a parameter like any other
        self.e_bias = self.create_parameter([e], default_initializer=init)
        self.experts_gate_up = self.create_parameter(
            [e, c, 2 * i], default_initializer=init)
        self.experts_down = self.create_parameter(
            [e, i, c], default_initializer=init)
        self.shared_expert = Xing4MLP(c, i * cfg.n_shared_experts)
        self.last_touched = None

    def route(self, h):
        """Where each token of ``h`` ``[T, C]`` goes: the chosen experts
        ``[T, k]`` (an array) and their weights ``[T, k]``. The scores
        are float32 whatever ``h`` is served in."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            scores = dispatch.apply("moe_scores", moe_scores,
                                    (h, self.gate_weight), cache=False)
            idx = moe_choose(scores.value, self.e_bias.value,
                             cfg.num_experts_per_tok)
            w = dispatch.apply(
                "moe_weights", lambda sv: moe_weights(
                    sv, idx, scale=float(cfg.routed_scaling_factor),
                    renorm=bool(cfg.norm_topk_prob)),
                (scores,), cache=False)
        return idx, w

    def forward(self, x):
        shape = [int(d) for d in x.shape]
        h = x.reshape([-1, shape[-1]])
        idx, w = self.route(h)
        with jax.named_scope("moe_experts"):
            y = dispatch.apply(
                "moe_dispatch",
                lambda hv, wv, gu, dn: moe_dispatch(hv, idx, wv, gu, dn),
                (h, w, self.experts_gate_up, self.experts_down),
                cache=False)
            self.last_touched = experts_touched(
                idx, self.cfg.n_routed_experts)
        return (y + self.shared_expert(h)).reshape(shape)


# ------------------------------------------------------ hyper-connections
def hc_maps(x, phi, bias, alpha, *, iters, eps, lo, hi):
    """The three maps of one sub-layer from the streams ``x`` ``[T, n,
    C]``: ``Hpre`` ``[n, T]``, ``Hpost`` ``[n, T]``, ``Hres`` ``[n, n,
    T]`` (float32, tokens along the lanes)."""
    t, n, c = x.shape
    xf = x.reshape(t, n * c).astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    z = jnp.einsum("km,tk->mt", phi, xf.astype(phi.dtype),
                   preferred_element_type=_F32)
    a = alpha.astype(_F32)
    bias = bias.astype(_F32)[:, None]
    h_pre = jax.nn.sigmoid(a[0] * z[:n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[2 * n:] + bias[2 * n:], lo, hi)
                ).reshape(n, n, t)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # columns
    return h_pre, h_post, m


def hc_pre(x, phi, bias, alpha, **kw):
    """``x`` ``[T, n, C]`` -> the sub-layer's input ``Hpre X`` ``[T,
    C]`` and the two maps its output is mixed back with."""
    h_pre, h_post, h_res = hc_maps(x, phi, bias, alpha, **kw)
    xf = x.astype(_F32)
    h = sum(h_pre[j][:, None] * xf[:, j] for j in range(x.shape[1]))
    return h.astype(x.dtype), h_post, h_res


def hc_post(x, f, h_post, h_res):
    """``Hres X + Hpost[:, None] * f``: ``x`` ``[T, n, C]``, the
    sub-layer's output ``f`` ``[T, C]``."""
    n = x.shape[1]
    xf, ff = x.astype(_F32), f.astype(_F32)
    rows = []
    for i in range(n):
        acc = h_post[i][:, None] * ff
        for j in range(n):
            acc = acc + h_res[i, j][:, None] * xf[:, j]
        rows.append(acc)
    return jnp.stack(rows, axis=1).astype(x.dtype)


class Xing4HyperConnection(nn.Layer):
    """The maps of one sub-layer: ``phi`` ``[n C, n + n + n n]`` (pre |
    post | res), ``bias`` the same width, ``alpha`` the three gates.
    ``phi`` starts normal with std ``(n C)^-0.5``, so the maps' logits
    have unit spread at any width and ``Hres`` is neither the identity
    nor uniform."""

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        n, c = cfg.hc_mult, cfg.hidden_size
        self.kw = dict(iters=int(cfg.hc_sinkhorn_iters),
                       eps=float(cfg.hc_eps),
                       lo=float(cfg.mhc_h_res_clamp_min),
                       hi=float(cfg.mhc_h_res_clamp_max))
        width = 2 * n + n * n
        self.phi = self.create_parameter(
            [n * c, width],
            default_initializer=I.Normal(0.0, (n * c) ** -0.5))
        self.bias = self.create_parameter(
            [width], is_bias=True, default_initializer=I.Constant(0.0))
        self.alpha = self.create_parameter(
            [3], default_initializer=I.Constant(1.0))

    def pre(self, x):
        with jax.named_scope("hc_mix"):
            return dispatch.apply("hc_pre", hc_pre,
                                  (x, self.phi, self.bias, self.alpha),
                                  self.kw, cache=False)

    def post(self, x, f, h_post, h_res):
        with jax.named_scope("hc_mix"):
            return dispatch.apply("hc_post", hc_post,
                                  (x, f, h_post, h_res), cache=False)


# ----------------------------------------------------------------- layers
class Xing4DecoderLayer(nn.Layer):
    def __init__(self, cfg: Xing4Config, dense: bool):
        super().__init__()
        self.attn_hc = Xing4HyperConnection(cfg)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = Xing4Attention(cfg)
        self.ffn_hc = Xing4HyperConnection(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = (Xing4MLP(cfg.hidden_size, cfg.intermediate_size)
                    if dense else Xing4MoE(cfg))

    def forward(self, x, cos, sin, cache=None, pos=None, page_table=None):
        """``x`` ``[B, S, n, C]`` -> ``(x, new_cache)``."""
        b, s, n, c = (int(d) for d in x.shape)
        x = x.reshape([b * s, n, c])
        h, h_post, h_res = self.attn_hc.pre(x)
        a, new_cache = self.self_attn(
            self.input_layernorm(h.reshape([b, s, c])), cos, sin,
            cache=cache, pos=pos, page_table=page_table)
        x = self.attn_hc.post(x, a.reshape([b * s, c]), h_post, h_res)
        h, h_post, h_res = self.ffn_hc.pre(x)
        f = self.mlp(self.post_attention_layernorm(h))
        x = self.ffn_hc.post(x, f, h_post, h_res)
        return x.reshape([b, s, n, c]), new_cache


def _positions(pos, b, s):
    """Positions ``[B or 1, S]`` of the tokens being fed."""
    if pos is None:
        return jnp.arange(s)[None]
    p = jnp.asarray(pos.value if hasattr(pos, "value") else pos)
    if p.ndim == 0:
        return (p + jnp.arange(s))[None]
    return p[:, None] + jnp.arange(s)[None]


class Xing4Model(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            Xing4DecoderLayer(cfg, dense=i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def rope_at(self, positions):
        """cos and sin ``[B or 1, S, dr / 2]`` (float32) at integer
        ``positions``."""
        cfg = self.config
        f = positions.astype(_F32)[..., None] * yarn_inv_freq(
            cfg.qk_rope_head_dim, float(cfg.rope_theta), cfg.rope_scaling)
        return jnp.cos(f), jnp.sin(f)

    def run_layers(self, h, layers, cos, sin, caches=None, pos=None,
                   page_table=None):
        """``h`` ``[B, S, C]`` replicated over the streams, through
        ``layers``, summed over the streams again: ``(h, new_caches)``."""
        b, s, c = (int(d) for d in h.shape)
        n = self.config.hc_mult
        x = dispatch.apply(
            "hc_expand",
            lambda v: jnp.broadcast_to(v[:, :, None], (b, s, n, c)),
            (h,), cache=False)
        new_caches = []
        for i, layer in enumerate(layers):
            cache = None if caches is None else caches[i][0]
            x, c2 = layer(x, cos, sin, cache=cache, pos=pos,
                          page_table=page_table)
            new_caches.append((c2,))
        return x.sum(axis=2), new_caches

    def forward(self, input_ids, caches=None, pos=None, page_table=None,
                apply_final_norm=True):
        """``caches``: a one-array tuple a layer (``alloc_kv_caches``);
        with ``page_table`` the arrays are page arenas. Returns the
        hidden state, and the new caches with it when given any."""
        b, s = int(input_ids.shape[0]), int(input_ids.shape[1])
        if pos is not None:
            pos = jnp.asarray(pos.value if hasattr(pos, "value") else pos)
        if page_table is not None:
            page_table = jnp.asarray(
                page_table.value if hasattr(page_table, "value")
                else page_table)
        cos, sin = self.rope_at(_positions(pos, b, s))
        h, new_caches = self.run_layers(
            self.embed_tokens(input_ids), self.layers, cos, sin, caches,
            pos, page_table)
        if apply_final_norm:
            h = self.norm(h)
        return h if caches is None else (h, new_caches)


class Xing4MTP(nn.Layer):
    """One multi-token-prediction module (DeepSeek-V3's): the main
    model's hidden state at ``i`` and the embedding of token ``i + 1``,
    each normed, joined and projected, through one expert block with
    its own residual streams; the embedding and the head are the main
    model's."""

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        c = cfg.hidden_size
        self.hnorm = nn.RMSNorm(c, cfg.rms_norm_eps)
        self.enorm = nn.RMSNorm(c, cfg.rms_norm_eps)
        self.eh_proj = nn.Linear(2 * c, c, bias_attr=False)
        self.block = Xing4DecoderLayer(cfg, dense=False)
        self.norm = nn.RMSNorm(c, cfg.rms_norm_eps)


class Xing4ForCausalLM(nn.Layer):
    def __init__(self, config: Xing4Config):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError("Xing4: the head is not tied in this family")
        self.config = config
        self.model = Xing4Model(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        self.mtp = (Xing4MTP(config)
                    if config.num_nextn_predict_layers else None)

    # ``generation.prefill`` reads this: it needs one row of logits,
    # and says which through ``head_row``
    head_takes_row = True

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None,
                page_table=None, head_row=None):
        """The seam every decoder of this package serves through:
        logits ``[B, S, V]``, and with ``caches`` the new caches too.
        ``head_row`` (scalar, traceable) runs the final norm and the
        head on that one position alone, logits ``[B, 1, V]``: a
        bucketed prefill wants one row of 4096, and the other rows of a
        131072-wide head are 3.8 TFLOP and a gigabyte."""
        if attn_mask is not None:
            raise ValueError("Xing4: no explicit attention mask "
                             "(positions mask the cache)")
        out = self.model(input_ids, caches=caches, pos=pos,
                         page_table=page_table, apply_final_norm=False)
        h, new_caches = (out, None) if caches is None else out
        if head_row is not None:
            h = dispatch.apply(
                "head_row", lambda v: jax.lax.dynamic_slice_in_dim(
                    v, jnp.asarray(head_row, jnp.int32), 1, axis=1),
                (h,), cache=False)
        logits = self.lm_head(self.model.norm(h))
        return logits if caches is None else (logits, new_caches)

    def pop_step_counters(self):
        """What the step just traced counted, for the serving engine to
        return beside the next tokens: ``experts_touched``, the experts
        that got at least one token, summed over the expert layers."""
        total = jnp.zeros((), jnp.int32)
        for layer in self.model.layers:
            t = getattr(layer.mlp, "last_touched", None)
            if t is not None:
                total = total + t
                layer.mlp.last_touched = None
        return {"experts_touched": total}

    def mtp_logits(self, input_ids):
        """Logits ``[B, S - 1, V]`` of the MTP module: row ``i`` joins
        the main model's last hidden state at ``i`` (streams summed,
        before the final norm) with token ``i + 1`` and predicts token
        ``i + 2``. No cache: training and tests."""
        m = self.mtp
        if m is None:
            raise ValueError("built with num_nextn_predict_layers 0")
        b, s = int(input_ids.shape[0]), int(input_ids.shape[1])
        h = self.model(input_ids, apply_final_norm=False)[:, :s - 1]
        e = self.model.embed_tokens(input_ids[:, 1:])
        from ..ops.manipulation import concat

        x = m.eh_proj(concat([m.hnorm(h), m.enorm(e)], axis=-1))
        cos, sin = self.model.rope_at(_positions(None, b, s - 1))
        x, _ = self.model.run_layers(x, [m.block], cos, sin)
        return self.lm_head(m.norm(x))

    def num_params(self):
        return sum(int(p.size) for p in self.parameters())

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=0, cache_dtype=None):
        from .generation import DEFAULT_CACHE_DTYPE
        from .generation import generate as _generate

        return _generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p, eos_token_id=eos_token_id, seed=seed,
            cache_dtype=cache_dtype or DEFAULT_CACHE_DTYPE,
        )
