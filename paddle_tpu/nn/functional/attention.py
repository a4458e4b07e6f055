"""Attention functionals: scaled_dot_product_attention / flash_attention.

Reference parity: python/paddle/nn/functional/flash_attention.py wrapping the
phi FlashAttnKernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu — unverified,
mount empty). TPU redesign: the fused path is a Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py); this module is the API surface that
picks Pallas on TPU and the jnp composed fallback elsewhere. Layouts follow
paddle: q/k/v are [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core import random as random_mod


def _sdpa_ref(q, k, v, mask, *, causal, scale, dropout_p, key):
    # q,k,v: [B, S, H, D] -> compute in [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(causal_mask, s, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, -jnp.inf)
        else:
            s = s + mask
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(out, 1, 2)


def _sdpa_grouped_ref(q, k, v, mask, *, scale):
    """``_sdpa_ref`` for grouped-query attention over a cache, K and V
    NOT repeated: q ``[B, S, H, D]`` against k/v ``[B, S_k, kvH, D]``,
    ``H = kvH * rep``, query head ``h = g * rep + r`` attending KV head
    ``g`` (the order ``repeat_interleave(rep, axis=2)`` gives). ``mask``
    is additive and broadcasts over ``[B, H, S, S_k]``. Same op order
    (scale after the score contraction, mask added, fp32 softmax, cast,
    value contraction); each KV head is read once, not ``rep`` times."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, sq, kvh, rep, d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) * scale
    if mask is not None:
        mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)
        if mask.shape[1] == 1:
            mask = mask[:, :, None]
        else:  # a per-head mask: split its head axis as q's was
            mask = mask.reshape(mask.shape[0], kvh, rep, *mask.shape[2:])
        s = s + mask
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
    return out.reshape(b, sq, h, d)


def _use_pallas(q):
    """Pallas flash attention on real TPU; composed jnp elsewhere (CPU CI)."""
    try:
        import jax as _j

        return any(d.platform != "cpu" for d in _j.devices())
    except Exception:
        return False


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
):
    scale = 1.0 / math.sqrt(query.shape[-1])
    dp = float(dropout_p) if training else 0.0
    rng = random_mod.next_key() if dp > 0.0 else None

    if attn_mask is None and dp == 0.0 and _use_pallas(query):
        from ...kernels import flash_attention as fa

        def _fa(qv, kv, vv):
            return fa.flash_attention_fwd(qv, kv, vv, causal=is_causal, scale=scale)

        return dispatch.apply("flash_attention", _fa, (query, key, value), cache=False)

    def _sdpa(qv, kv, vv, mv):
        return _sdpa_ref(
            qv, kv, vv, mv, causal=is_causal, scale=scale, dropout_p=dp, key=rng
        )

    return dispatch.apply(
        "scaled_dot_product_attention",
        _sdpa,
        (query, key, value, attn_mask),
        cache=False,
    )


def grouped_query_cache_attention(query, key, value, attn_mask):
    """Inference attention of ``query`` ``[B, S, H, D]`` over a cache
    view ``key``/``value`` ``[B, S_k, kvH, D]`` with FEWER heads than
    the query (GQA), under an additive ``attn_mask`` — the composed body
    of :func:`scaled_dot_product_attention` (same op name for AMP, same
    op order) without first repeating K and V to ``H`` heads."""
    scale = 1.0 / math.sqrt(query.shape[-1])

    def _sdpa(qv, kv, vv, mv):
        return _sdpa_grouped_ref(qv, kv, vv, mv, scale=scale)

    return dispatch.apply(
        "scaled_dot_product_attention",
        _sdpa,
        (query, key, value, attn_mask),
        cache=False,
    )


def flash_attention(
    query,
    key,
    value,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training
    )
    if return_softmax:
        return out, None
    return out, None if return_softmax else None


def flash_attn_unpadded(
    query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k,
    scale, dropout=0.0, causal=False, return_softmax=False, training=True,
    name=None,
):
    """Varlen flash attention: segment-masked single-sequence attention.

    The packed [total_tokens, H, D] layout is attended with a block-diagonal
    mask derived from cu_seqlens (reference: phi FlashAttnUnpaddedKernel).
    """
    import numpy as np

    cu_q = np.asarray(
        cu_seqlens_q.numpy() if hasattr(cu_seqlens_q, "numpy") else cu_seqlens_q
    )

    def _varlen(qv, kv, vv):
        total = qv.shape[0]
        seg = jnp.zeros((total,), jnp.int32)
        for i in range(len(cu_q) - 1):
            seg = seg.at[cu_q[i] : cu_q[i + 1]].set(i)
        s = jnp.einsum("qhd,khd->hqk", qv, kv) * scale
        seg_mask = seg[:, None] == seg[None, :]
        if causal:
            pos = jnp.arange(total)
            seg_mask = seg_mask & (pos[None, :] <= pos[:, None])
        s = jnp.where(seg_mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(qv.dtype)
        return jnp.einsum("hqk,khd->qhd", p, vv)

    out = dispatch.apply("flash_attn_unpadded", _varlen, (query, key, value), cache=False)
    return out, None
