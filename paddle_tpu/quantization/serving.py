"""quantize_for_serving — real int8 weight-only execution for deploy.

The PTQ/QAT stack simulates quantization (fake-quant: scales learned,
arithmetic still wide). This pass makes it REAL for the serving/decode
path: every eligible ``nn.Linear`` (and every PTQ/QAT-converted
``ObservedLayer`` wrapping one) is replaced by a
:class:`QuantizedLinear` that stores its weight as **int8 values + a
per-output-channel fp32 scale** — registered as persistable buffers,
so the narrow weights flow unchanged through ``state_dict``,
``jit.save`` artifacts (``Predictor.into_engine()`` serves them), and
the serving engines' weight snapshots. Forward is the composed
dequant -> matmul (:func:`int8_matmul_composed`): XLA fuses the
dequantization into the matmul's weight load.

The pass is IDEMPOTENT: quantizing an already-quantized model returns
it unchanged (already-int8 weights must never be re-quantized — a
second rounding pass would silently degrade them; tier-1-pinned).

Scale derivation: an ``ObservedLayer`` carrying a per-channel observed
weight scale keeps its CALIBRATED scales (the PTQ/QAT -> serve chain);
a bare Linear (or a per-tensor observed scale) gets fresh symmetric
absmax-per-output-channel scales from the weight itself — for
weight-only quantization the weight is fully known, so calibration
data is not required.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..core import dispatch
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from .qat import ObservedLayer, _swap_layers


def quantize_weight_with_scales(w, scale):
    """The ONE home of the int8 weight rounding rule: float ``[in,
    out]`` weight + per-out-channel fp32 ``[out]`` scales -> int8
    values. Fresh-absmax and PTQ-calibrated callers both round here,
    so the two deploy paths can never drift apart."""
    wf = jnp.asarray(w).astype(jnp.float32)
    s = jnp.maximum(jnp.asarray(scale, jnp.float32), 1e-8)
    q = jnp.clip(
        jnp.round(wf / s[None, :]), -127, 127
    ).astype(jnp.int8)  # tpu-lint: quant
    return q, s


def quantize_weight(w):
    """Float ``[in, out]`` weight -> (int8 values, fp32 per-out-channel
    scales ``[out]``). Symmetric absmax over the contraction axis."""
    wf = jnp.asarray(w).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)
    return quantize_weight_with_scales(wf, absmax / 127.0)


def int8_matmul_composed(x, w_q, scale):
    """``x @ dequant(w_q, scale)``: x ``[..., H]`` float, w_q int8
    ``[H, N]``, scale fp32 ``[N]``; returns ``[..., N]`` in x's dtype.
    Dequantize the whole weight (int8 -> fp32 * scale -> x's dtype),
    then ONE dot over the full contraction dim."""
    shape = x.shape
    h = int(shape[-1])
    x2d = x.reshape(-1, h)
    n_out = int(w_q.shape[1])
    sc = scale.reshape(1, n_out).astype(jnp.float32)
    w = (w_q.astype(jnp.float32) * sc).astype(x2d.dtype)  # tpu-lint: quant
    return jnp.dot(x2d, w).reshape(tuple(shape[:-1]) + (n_out,))


class QuantizedLinear(Layer):
    """Weight-only int8 Linear: ``y = x @ dequant(weight_q, scale) + b``.

    ``weight_q`` (int8 ``[in, out]``) and ``weight_scale`` (fp32
    ``[out]``) are persistable BUFFERS — not parameters — so optimizer
    walks skip them while snapshots/exports carry them."""

    def __init__(self, weight_q, weight_scale, bias=None):
        super().__init__()
        wq = jnp.asarray(weight_q)
        ws = jnp.asarray(weight_scale, jnp.float32)
        if wq.dtype != jnp.int8:
            raise ValueError(f"weight_q must be int8, got {wq.dtype}")
        if wq.ndim != 2 or ws.shape != (wq.shape[1],):
            raise ValueError(
                f"expected weight_q [in, out] with per-out-channel "
                f"scale [out]; got {wq.shape} / {ws.shape}"
            )
        self.in_features = int(wq.shape[0])
        self.out_features = int(wq.shape[1])
        self.register_buffer("weight_q", Tensor(wq, stop_gradient=True))
        self.register_buffer("weight_scale",
                             Tensor(ws, stop_gradient=True))
        if bias is not None:
            self.register_buffer(
                "bias", Tensor(jnp.asarray(
                    bias.value if isinstance(bias, Tensor) else bias
                ), stop_gradient=True)
            )
        else:
            self.bias = None

    def forward(self, x):
        # weight-only decode is a no-grad path: the op registers
        # nondiff (train-time quantization goes through the QAT
        # fake-quant STE instead)
        y = dispatch.apply(
            "int8_matmul", int8_matmul_composed,
            (x, self.weight_q, self.weight_scale), nondiff=True,
        )
        if self.bias is not None:
            y = y + self.bias
        return y

    def extra_repr(self):
        return (f"in_features={self.in_features}, "
                f"out_features={self.out_features}, dtype=int8")


def _is_linear(layer):
    from ..nn.layer.common import Linear

    return isinstance(layer, Linear)


def _from_linear(lin):
    wq, ws = quantize_weight(lin.weight.value)
    return QuantizedLinear(wq, ws, bias=lin.bias)


def _from_observed(obs):
    inner = obs._inner
    if not _is_linear(inner):
        return None
    ws = obs.weight_scale
    per_channel = (
        ws is not None
        and int(obs.weight_bits) == 8
        and np.ndim(ws) == 1
        and np.shape(ws)[0] == int(inner.weight.shape[-1])
    )
    if per_channel:
        # CALIBRATED scales (the PTQ/QAT observed absmax): divide by
        # the frozen scale instead of deriving a fresh one
        wq, s = quantize_weight_with_scales(inner.weight.value, ws)
        return QuantizedLinear(wq, s, bias=inner.bias)
    # per-tensor / non-8-bit observed scales: fall back to fresh
    # per-channel absmax (strictly tighter than a per-tensor scale)
    return _from_linear(inner)


def quantize_for_serving(model, inplace=False):
    """Convert a trained / PTQ'd / QAT-converted model's Linear weights
    to ``(int8, scale)`` pairs executed as dequant -> matmul.

    Returns the converted model (a deep copy unless ``inplace=True``).
    Calling it again on the result is a no-op (idempotent)."""
    if not inplace:
        import copy

        model = copy.deepcopy(model)

    def make(layer):
        if isinstance(layer, QuantizedLinear):
            return None  # idempotence: never re-round int8 weights
        if isinstance(layer, ObservedLayer):
            return _from_observed(layer)
        if _is_linear(layer):
            return _from_linear(layer)
        return None

    return _swap_layers(model, make)
