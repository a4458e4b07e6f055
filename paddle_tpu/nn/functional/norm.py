"""Normalization functionals.

Reference parity: python/paddle/nn/functional/norm.py + phi fused norm
kernels (unverified, mount empty). The reference ships hand-fused CUDA
RMS/LayerNorm kernels (paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu
— unverified); here the default path is plain jnp (XLA fuses it well) and
paddle_tpu.kernels provides Pallas versions behind the same API for the
cases XLA's fusion leaves bandwidth on the table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import dispatch
from ...core import enforce as _enf


def _batch_norm_infer(x, mean, var, w, b, *, eps, channel_axis):
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    inv = jax.lax.rsqrt(var.reshape(shape) + eps)
    out = (x - mean.reshape(shape)) * inv
    if w is not None:
        out = out * w.reshape(shape)
    if b is not None:
        out = out + b.reshape(shape)
    return out


def _batch_norm_train(x, w, b, *, eps, channel_axis):
    axes = tuple(i for i in range(x.ndim) if i != channel_axis)
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    inv = jax.lax.rsqrt(var.reshape(shape) + eps)
    out = (x - mean.reshape(shape)) * inv
    if w is not None:
        out = out * w.reshape(shape)
    if b is not None:
        out = out + b.reshape(shape)
    return out, mean, var


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-05,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    use_global = (use_global_stats is True) or not training
    if use_global:
        return dispatch.apply(
            "batch_norm_infer",
            _batch_norm_infer,
            (x, running_mean, running_var, weight, bias),
            {"eps": float(epsilon), "channel_axis": channel_axis},
        )
    out, batch_mean, batch_var = dispatch.apply(
        "batch_norm_train",
        _batch_norm_train,
        (x, weight, bias),
        {"eps": float(epsilon), "channel_axis": channel_axis},
    )
    # update running stats in place (paddle: r = m*r + (1-m)*batch)
    if running_mean is not None:
        from ...core import tape

        with tape.no_grad():
            running_mean.value = (
                momentum * running_mean.value + (1 - momentum) * batch_mean.value
            )
            running_var.value = (
                momentum * running_var.value + (1 - momentum) * batch_var.value
            )
    return out


def _layer_norm(x, w, b, *, eps, begin_axis):
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        out = out * w.reshape(x.shape[begin_axis:])
    if b is not None:
        out = out + b.reshape(x.shape[begin_axis:])
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    ns = (
        (normalized_shape,)
        if isinstance(normalized_shape, int)
        else tuple(normalized_shape)
    )
    begin_axis = x.ndim - len(ns)
    _enf.enforce(
        begin_axis >= 0 and tuple(
            int(d) for d in x.shape[begin_axis:]
        ) == tuple(int(d) for d in ns),
        "layer_norm",
        "normalized_shape {} must match the trailing dims of input "
        "shape {}", tuple(ns), tuple(x.shape),
    )
    return dispatch.apply(
        "layer_norm",
        _layer_norm,
        (x, weight, bias),
        {"eps": float(epsilon), "begin_axis": begin_axis},
    )


def _rms_norm(x, w, b, *, eps, begin_axis):
    axes = tuple(range(begin_axis, x.ndim))
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes, keepdims=True)
    out = (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)).astype(x.dtype)
    if w is not None:
        out = out * w
    if b is not None:
        out = out + b
    return out


def _fused_rms_available(x, weight, bias, begin_axis):
    """Pallas fused path: TPU, last-axis norm, weight-only. fp16 is
    excluded — the Mosaic TPU dialect rejects f16 ('Unsupported type in
    mosaic dialect'); fp16 AMP runs use the composed path, which XLA
    fuses anyway."""
    if bias is not None or weight is None:
        return False
    if begin_axis != x.ndim - 1:
        return False
    if str(getattr(x, "dtype", "")) == "float16":
        return False
    from ...kernels import autotune

    return not autotune.interpret_mode() and not autotune.spmd_refusal(
        "rms_norm")


def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1, name=None):
    begin_axis = begin_norm_axis % x.ndim
    if _fused_rms_available(x, weight, bias, begin_axis):
        from ...kernels.rms_norm import rms_norm_fused

        def _fused(xv, wv):
            return rms_norm_fused(xv, wv, float(epsilon))

        return dispatch.apply(
            "fused_rms_norm", _fused, (x, weight), cache=False
        )
    return dispatch.apply(
        "rms_norm",
        _rms_norm,
        (x, weight, bias),
        {"eps": float(epsilon), "begin_axis": begin_axis},
    )


def _group_norm(x, w, b, *, groups, eps, channel_axis):
    if channel_axis != 1:
        x = jnp.moveaxis(x, channel_axis, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    xg = x.reshape((n, groups, c // groups) + spatial)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    shape = (1, c) + (1,) * len(spatial)
    if w is not None:
        out = out * w.reshape(shape)
    if b is not None:
        out = out + b.reshape(shape)
    if channel_axis != 1:
        out = jnp.moveaxis(out, 1, channel_axis)
    return out


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    return dispatch.apply(
        "group_norm",
        _group_norm,
        (x, weight, bias),
        {"groups": int(num_groups), "eps": float(epsilon), "channel_axis": channel_axis},
    )


def _instance_norm(x, w, b, *, eps):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        out = out * w.reshape(shape)
    if b is not None:
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        out = out + b.reshape(shape)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    return dispatch.apply(
        "instance_norm", _instance_norm, (x, weight, bias), {"eps": float(eps)}
    )


def _normalize(x, *, p, axis, eps):
    if p == 2:
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    else:
        n = jnp.power(
            jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p
        )
    return x / jnp.maximum(n, eps)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return dispatch.apply(
        "normalize",
        _normalize,
        (x,),
        {"p": float(p), "axis": int(axis), "eps": float(epsilon)},
    )


def local_response_norm(x, size, alpha=0.0001, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def _lrn(xv):
        sq = jnp.square(xv)
        half = size // 2
        c = xv.shape[1]
        pads = [(0, 0)] * xv.ndim
        pads[1] = (half, size - half - 1)
        sq_p = jnp.pad(sq, pads)
        acc = sum(
            jax.lax.slice_in_dim(sq_p, i, i + c, axis=1) for i in range(size)
        )
        return xv / jnp.power(k + alpha * acc, beta)

    return dispatch.apply("local_response_norm", _lrn, (x,), cache=False)
