"""Weight-only int8 matmul with in-kernel dequant epilogue — Pallas TPU.

The serving decode step is weight-bandwidth bound: every projection
streams its full weight matrix from HBM to multiply one token per
resident row. ``quantize_for_serving`` stores those weights as int8
values + per-output-channel fp32 scales (quantization/serving.py);
this kernel consumes them directly — the int8 block is dequantized in
VMEM as part of the weight load's epilogue and fed straight into its
output tile's matmul, so the wide weight NEVER exists in HBM and the
bytes crossing the HBM bus drop ~2x vs bf16 (~4x vs fp32). This is the
FlashFuser move (PAPERS.md) applied to dequantization: fold the
producer into the consumer instead of materializing the intermediate.

Bit-exactness discipline (the PR 6 fusion-kernel contract): the kernel
tile computes ``x_block @ ((w_q_block * scale_block) cast to x.dtype)``
— elementwise dequant then ONE dot over the full contraction dim, the
exact op order of :func:`int8_matmul_composed` — so fused and composed
are pinned EQUAL in CI (fwd only: this is the no-grad decode path).

Selection is tune-cache OPT-IN (:func:`int8_matmul_select`), same
discipline as the other fused kernels: no measured entry for the exact
(shape, device) -> the composed dequant->matmul runs byte-identical;
``fused_beats_composed=False`` entries are honored as measured policy;
stale/illegal cached configs are counted one-shot-warned fallbacks.
Block sizes (block_rows, block_cols) are the tuned knobs
(``autotune.int8_matmul_candidates``).

Falls back to pallas interpret mode off-TPU (CI) — same code path,
host execution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune


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


def _dequant(w_q, scale, dtype):
    """The shared dequant op order: int8 -> fp32 * scale -> compute
    dtype. ONE home so kernel and composed can never round apart."""
    return (
        w_q.astype(jnp.float32) * scale
    ).astype(dtype)  # tpu-lint: quant


def _int8_kernel(x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    w = _dequant(w_ref[:], s_ref[:], x_ref.dtype)   # [H, bc] in VMEM
    # the MXU accumulates in fp32 (Mosaic refuses a narrower accumulator)
    o_ref[:] = jnp.dot(
        x_ref[:], w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def int8_matmul(x, w_q, scale, block_rows=None, block_cols=None):
    """``x @ dequant(w_q, scale)`` in one kernel. x: [..., H] float;
    w_q: int8 [H, N]; scale: fp32 [N]. Returns [..., N] in x's dtype."""
    shape = x.shape
    h = int(shape[-1])
    x2d = x.reshape(-1, h)
    rows, n_out = int(x2d.shape[0]), int(w_q.shape[1])
    br, bc = _resolve_blocks(rows, n_out, block_rows, block_cols)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, out_dtype=x2d.dtype),
        grid=(rows // br, n_out // bc),
        in_specs=[
            pl.BlockSpec((br, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n_out), x2d.dtype),
        interpret=autotune.interpret_mode(),
        name="int8_matmul",
    )(x2d, w_q, scale.reshape(1, n_out).astype(jnp.float32))
    return out.reshape(tuple(shape[:-1]) + (n_out,))


def int8_matmul_composed(x, w_q, scale):
    """Composed reference: dequantize the whole weight, then matmul —
    op-for-op the kernel's math without the fusion (the wide weight
    materializes in HBM; skipping that copy is the kernel's win). The
    parity tests pin the two equal; untuned call sites run this."""
    shape = x.shape
    h = int(shape[-1])
    x2d = x.reshape(-1, h)
    n_out = int(w_q.shape[1])
    w = _dequant(w_q, scale.reshape(1, n_out).astype(jnp.float32),
                 x2d.dtype)
    return jnp.dot(x2d, w).reshape(tuple(shape[:-1]) + (n_out,))


def _resolve_blocks(rows, n_out, block_rows, block_cols):
    if block_rows is None or block_cols is None:
        cands = autotune.int8_matmul_candidates(rows, n_out)
        if not cands:
            raise ValueError(
                f"rows={rows} n_out={n_out} have no legal block config"
            )
        block_rows = block_rows or cands[0]["block_rows"]
        block_cols = block_cols or cands[0]["block_cols"]
    if rows % int(block_rows) or n_out % int(block_cols):
        raise ValueError(
            f"blocks ({block_rows}, {block_cols}) do not divide "
            f"({rows}, {n_out})"
        )
    return int(block_rows), int(block_cols)


def int8_matmul_select(rows, hidden, n_out):
    """Tune-cache OPT-IN selection: the fused kernel's config when a
    measured entry exists for this exact shape on this device, else
    None (call sites keep the composed dequant->matmul)."""
    sig = autotune.int8_matmul_sig(rows, hidden, n_out)
    entry = autotune.lookup_entry("int8_matmul", sig)
    if entry is None:
        return None
    cfg = dict(entry["config"])
    if not autotune.int8_matmul_config_legal(rows, n_out, cfg):
        autotune.note_fallback(
            "int8_matmul", sig, "stale-config",
            detail=f"cached {cfg} illegal for ({rows}, {n_out})",
        )
        return None
    if entry.get("fused_beats_composed") is False:
        autotune.note_selection("int8_matmul", "composed:measured")
        return None
    if autotune.spmd_refusal("int8_matmul"):
        return None
    autotune.note_selection("int8_matmul", "fused:cached")
    return cfg


def _apply_fused(xv, wqv, sv, *, block_rows, block_cols):
    return int8_matmul(xv, wqv, sv, block_rows=block_rows,
                       block_cols=block_cols)


def _apply_composed(xv, wqv, sv):
    return int8_matmul_composed(xv, wqv, sv)


def int8_matmul_apply(x, w_q, scale, *, config=None):
    """Tensor-level entry for model code. ``config`` (from
    :func:`int8_matmul_select`) activates the fused kernel; None runs
    the composed path. Weight-only decode is a no-grad path — the op
    registers nondiff (train-time quantization goes through the QAT
    fake-quant STE instead)."""
    from ..core import dispatch

    if config is not None:
        return dispatch.apply(
            "int8_matmul", _apply_fused, (x, w_q, scale),
            {"block_rows": int(config["block_rows"]),
             "block_cols": int(config["block_cols"])},
            nondiff=True,
        )
    return dispatch.apply(
        "int8_matmul", _apply_composed, (x, w_q, scale), nondiff=True,
    )
