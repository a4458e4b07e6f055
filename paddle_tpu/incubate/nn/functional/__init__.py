"""Fused-op functional API (paddle.incubate.nn.functional parity).

Reference parity: python/paddle/incubate/nn/functional/* backed by the phi
fusion kernels (paddle/phi/kernels/fusion/gpu/ — unverified, mount empty):
fused_rms_norm, fused_layer_norm, fused_rotary_position_embedding, swiglu,
fused_dropout_add, fused_linear, fused_linear_activation.

TPU design: on TPU the heavy ones (rms_norm, rope) route to Pallas kernels
(paddle_tpu/kernels/); the rest are composed jnp that XLA fuses inside
compiled steps. Layouts follow paddle: attention tensors are
[batch, seq, heads, head_dim].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core import dispatch
from ....core import random as random_mod
from ....core.tensor import Tensor


# ----------------------------------------------------------------- rms norm
def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, name=None):
    """paddle.incubate.nn.functional.fused_rms_norm parity.

    Optionally adds ``bias`` and ``residual`` to ``x`` first (the fused
    bias+residual+norm pattern), then RMS-normalizes over the trailing
    axes from ``begin_norm_axis``. Returns (out, residual_out) when a
    residual is passed, else out — matching the reference.
    """
    if quant_scale != -1:
        raise NotImplementedError("quantized fused_rms_norm is not supported")
    from ....nn import functional as F

    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = F.rms_norm(
        x, norm_weight, norm_bias, epsilon=epsilon,
        begin_norm_axis=begin_norm_axis,
    )
    if residual is not None:
        return out, residual_out
    return out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None,
                     quant_scale=-1, name=None):
    """paddle.incubate.nn.functional.fused_layer_norm parity."""
    if quant_scale != -1:
        raise NotImplementedError("quantized fused_layer_norm is not supported")
    from ....nn import functional as F

    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    axis = begin_norm_axis % x.ndim
    shape = tuple(int(s) for s in x.shape[axis:])
    out = F.layer_norm(x, shape, weight=norm_weight, bias=norm_bias,
                       epsilon=epsilon)
    if residual is not None:
        return out, residual_out
    return out


# --------------------------------------------------------------------- rope
def _rope_neox(tv, c, s):
    from ....kernels import autotune

    if str(tv.dtype) == "float16" or autotune.spmd_refusal("rope"):
        # Mosaic TPU rejects f16 ('Unsupported type in mosaic dialect')
        # and a kernel GSPMD would have to partition; composed rotation
        # instead — XLA fuses it
        half = tv.shape[-1] // 2
        x1, x2 = tv[..., :half], tv[..., half:]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        return jnp.concatenate([o1, o2], axis=-1).astype(tv.dtype)
    from ....kernels.rope import rope_fused

    return rope_fused(tv, c, s)


def _rope_gptj(tv, c, s):
    # GPT-J interleaved style: pairs are (x[2i], x[2i+1])
    x1 = tv[..., 0::2]
    x2 = tv[..., 1::2]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    return jnp.stack([o1, o2], axis=-1).reshape(tv.shape)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0,
                                    name=None):
    """paddle.incubate.nn.functional.fused_rotary_position_embedding parity.

    q/k/v: [B, S, H, D]. sin/cos: broadcastable [1, S, 1, D] (reference
    layout) or half-dim [1, S, 1, D/2] tables, or None to derive from
    ``rotary_emb_base``. Returns the (q, k, v) tuple with the rotation
    applied to each non-None input. On TPU the neox-style rotation runs
    in the Pallas rope kernel (paddle_tpu/kernels/rope.py).
    """
    if time_major:
        raise NotImplementedError("time_major=True is not supported")
    lead = q if q is not None else (k if k is not None else v)
    if lead is None:
        return None, None, None
    S, D = int(lead.shape[1]), int(lead.shape[3])

    if cos is None or sin is None:
        from ....kernels.rope import build_rope_cache

        cos_h, sin_h = build_rope_cache(S, D, base=rotary_emb_base)
    else:
        cos_v = cos.value if isinstance(cos, Tensor) else jnp.asarray(cos)
        sin_v = sin.value if isinstance(sin, Tensor) else jnp.asarray(sin)
        cos_v = cos_v.reshape(1, -1, 1, cos_v.shape[-1])
        sin_v = sin_v.reshape(1, -1, 1, sin_v.shape[-1])
        if cos_v.shape[-1] == D:  # full-dim tables: two mirrored halves
            cos_h, sin_h = cos_v[..., : D // 2], sin_v[..., : D // 2]
        else:
            cos_h, sin_h = cos_v, sin_v
    if position_ids is not None:
        pid = (
            position_ids.value
            if isinstance(position_ids, Tensor)
            else jnp.asarray(position_ids)
        )
        cos_h = jnp.take(cos_h[0, :, 0, :], pid, axis=0)[:, :, None, :]
        sin_h = jnp.take(sin_h[0, :, 0, :], pid, axis=0)[:, :, None, :]

    fn = _rope_neox if use_neox_rotary_style else _rope_gptj
    op = "fused_rope" if use_neox_rotary_style else "fused_rope_gptj"
    cos_t, sin_t = Tensor(cos_h), Tensor(sin_h)

    def _one(t):
        if t is None:
            return None
        return dispatch.apply(op, fn, (t, cos_t, sin_t))

    return _one(q), _one(k), _one(v)


# ------------------------------------------------------------------- swiglu
def _swiglu_split(xv):
    x1, x2 = jnp.split(xv, 2, axis=-1)
    return jax.nn.silu(x1) * x2


def _swiglu2(xv, yv):
    return jax.nn.silu(xv) * yv


def swiglu(x, y=None, name=None):
    """paddle.incubate.nn.functional.swiglu parity: silu(x) * y.

    With y=None, x is split in half on the last axis: silu(x1) * x2.
    """
    if y is None:
        return dispatch.apply("swiglu_split", _swiglu_split, (x,))
    return dispatch.apply("swiglu", _swiglu2, (x, y))


# ------------------------------------------------------------ dropout + add
def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """paddle.incubate.nn.functional.fused_dropout_add parity."""
    pv = float(p)
    if not training or pv == 0.0:
        return x + y
    key = random_mod.next_key()
    upscale = mode == "upscale_in_train"

    def _fn(xv, yv):
        keep = jax.random.bernoulli(key, 1.0 - pv, xv.shape)
        if upscale:
            dropped = jnp.where(keep, xv / (1.0 - pv), 0.0)
        else:
            dropped = jnp.where(keep, xv, 0.0)
        return dropped.astype(xv.dtype) + yv

    # per-call rng key -> closure, uncached (same pattern as sdpa dropout)
    return dispatch.apply("fused_dropout_add", _fn, (x, y), cache=False)


# ------------------------------------------------------------------- linear
def _linear_fn(xv, wv, bv, *, trans_w):
    w = wv.T if trans_w else wv
    y = jnp.matmul(xv, w)
    return y if bv is None else y + bv


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """paddle.incubate.nn.functional.fused_linear parity (cublasLt fused
    gemm+epilogue upstream; one XLA fusion here)."""
    return dispatch.apply(
        "fused_linear", _linear_fn, (x, weight, bias),
        {"trans_w": bool(transpose_weight)},
    )


_ACTS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "none": lambda v: v}


def _linear_act_fn(xv, yv, bv, *, trans_x, trans_y, act):
    a = xv.T if trans_x else xv
    b = yv.T if trans_y else yv
    y = jnp.matmul(a, b)
    if bv is not None:  # None keeps the activation dtype (no f32 zeros)
        y = y + bv
    return _ACTS[act](y)


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """fused gemm + bias + activation epilogue."""
    return dispatch.apply(
        "fused_linear_activation", _linear_act_fn, (x, y, bias),
        {"trans_x": bool(trans_x), "trans_y": bool(trans_y),
         "act": activation},
    )


def fused_bias_dropout_residual_layer_norm(
    x, residual, bias=None, ln_scale=None, ln_bias=None, dropout_rate=0.5,
    ln_epsilon=1e-5, training=True, mode="upscale_in_train", name=None,
):
    """paddle.incubate.nn.functional.fused_bias_dropout_residual_layer_norm."""
    from ....nn import functional as F

    h = x if bias is None else x + bias
    h = fused_dropout_add(h, residual, p=dropout_rate, training=training,
                          mode=mode)
    shape = (int(h.shape[-1]),)
    return F.layer_norm(h, shape, weight=ln_scale, bias=ln_bias,
                        epsilon=ln_epsilon)


def ring_flash_attention(q, k, v, causal=True, axis=None, name=None):
    """Exact attention over a sep-sharded sequence (ring KV rotation).
    See paddle_tpu.parallel.sep_ops for the design notes."""
    from ....parallel.sep_ops import ring_flash_attention as _ring

    return _ring(q, k, v, causal=causal, axis=axis)


def ulysses_attention(q, k, v, causal=True, axis=None, name=None):
    """Exact attention over a sep-sharded sequence (head<->seq all-to-all)."""
    from ....parallel.sep_ops import ulysses_attention as _uly

    return _uly(q, k, v, causal=causal, axis=axis)


def fused_multi_head_attention(
    x, qkv_weight, linear_weight, pre_layer_norm=False, pre_ln_scale=None,
    pre_ln_bias=None, ln_scale=None, ln_bias=None, pre_ln_epsilon=1e-5,
    qkv_bias=None, linear_bias=None, cache_kv=None, attn_mask=None,
    dropout_rate=0.5, attn_dropout_rate=0.5, ln_epsilon=1e-5,
    training=True, mode="upscale_in_train", ring_id=-1, add_residual=True,
    num_heads=None, name=None,
):
    """paddle.incubate.nn.functional.fused_multi_head_attention parity:
    (pre/post-LN) -> one QKV gemm -> attention -> out proj -> dropout +
    residual. qkv_weight accepts the reference [3, H, D, E] layout or a
    flat [E, 3E] (qkv_bias correspondingly [3, H, D] or [3E])."""
    from ....nn import functional as F

    if cache_kv is not None:
        raise NotImplementedError(
            "cache_kv (decode-time KV caching) is not supported here"
        )
    if ring_id not in (-1, None):
        raise NotImplementedError(
            "ring_id tensor parallelism: use the fleet mp_layers instead"
        )
    e = int(x.shape[-1])
    qw = qkv_weight
    if len(qw.shape) == 4:  # [3, H, D, E] -> [E, 3E]
        if num_heads is None:
            num_heads = int(qw.shape[1])
        qw = qw.reshape([3 * num_heads * int(qw.shape[2]), e]).t()
        if qkv_bias is not None and len(qkv_bias.shape) == 3:
            qkv_bias = qkv_bias.reshape([-1])  # [3, H, D] -> [3E]
    elif num_heads is None:
        raise ValueError("num_heads is required with a flat qkv_weight")
    head_dim = e // num_heads

    residual = x
    h = x
    if pre_layer_norm:
        h = F.layer_norm(h, (e,), weight=pre_ln_scale, bias=pre_ln_bias,
                         epsilon=pre_ln_epsilon)
    b, s = int(h.shape[0]), int(h.shape[1])
    qkv = fused_linear(h, qw, qkv_bias)
    qkv = qkv.reshape([b, s, 3, num_heads, head_dim])
    out = F.scaled_dot_product_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], attn_mask=attn_mask,
        dropout_p=attn_dropout_rate, training=training,
    )
    out = fused_linear(out.reshape([b, s, e]), linear_weight, linear_bias)
    if add_residual:
        out = fused_dropout_add(out, residual, p=dropout_rate,
                                training=training, mode=mode)
    else:
        out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if not pre_layer_norm:
        out = F.layer_norm(out, (e,), weight=ln_scale, bias=ln_bias,
                           epsilon=ln_epsilon)
    return out


def fused_feedforward(
    x, linear1_weight, linear2_weight, linear1_bias=None, linear2_bias=None,
    ln1_scale=None, ln1_bias=None, ln2_scale=None, ln2_bias=None,
    dropout1_rate=0.5, dropout2_rate=0.5, activation="relu",
    ln1_epsilon=1e-5, ln2_epsilon=1e-5, pre_layer_norm=False,
    training=True, mode="upscale_in_train", ring_id=-1,
    add_residual=True, name=None,
):
    """paddle.incubate.nn.functional.fused_feedforward parity:
    (pre/post-LN) -> linear+act -> dropout -> linear -> dropout +
    residual."""
    from ....nn import functional as F

    if activation not in ("gelu", "relu"):
        raise ValueError(
            f"fused_feedforward supports gelu/relu, got {activation!r}"
        )
    e = int(x.shape[-1])
    residual = x
    h = x
    if pre_layer_norm:
        h = F.layer_norm(h, (e,), weight=ln1_scale, bias=ln1_bias,
                         epsilon=ln1_epsilon)
    h = fused_linear_activation(
        h, linear1_weight, linear1_bias, activation=activation
    )
    h = F.dropout(h, p=dropout1_rate, training=training, mode=mode)
    h = fused_linear(h, linear2_weight, linear2_bias)
    if add_residual:
        out = fused_dropout_add(h, residual, p=dropout2_rate,
                                training=training, mode=mode)
    else:
        out = F.dropout(h, p=dropout2_rate, training=training, mode=mode)
    if not pre_layer_norm:
        out = F.layer_norm(out, (e,), weight=ln2_scale, bias=ln2_bias,
                           epsilon=ln2_epsilon)
    return out
