"""Fused RMSNorm + matmul epilogue — Pallas TPU kernel.

The model head runs ``rms_norm(h) @ W_lm`` (final norm + lm_head) in
both the train step and the serving/generation decode step. Unfused,
the normalized activation makes an HBM round trip between the two ops;
this kernel normalizes each row block in VMEM and feeds it straight
into its slice of the matmul — the normalized tensor never exists in
HBM. Grid tiles (row-block x col-block) of the output; the cheap norm
is recomputed per column block (O(rows*H) VPU work) to keep every grid
step independent.

Block sizes (block_rows, block_cols) are the tuned knobs
(``autotune.norm_matmul_candidates``). Backward runs through the
composed reference's VJP (same pattern as fused_rope_attention), so the
train step can select the fused forward too.

Selection is tune-cache OPT-IN (:func:`head_fusion_select`): with no
cache entry, call sites keep today's unfused path byte-identical.

Falls back to pallas interpret mode off-TPU (CI) — same code path, host
execution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


from . import autotune


def _normed_rows(x, w, eps):
    """fp32 RMSNorm of a row block, cast back to the activation dtype —
    op-for-op the math of kernels/rms_norm.py's forward (and the
    composed reference below; bit-exact parity is pinned in CI)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    return ((xf * rstd) * w.astype(jnp.float32)).astype(x.dtype)


def _fused_kernel(x_ref, w_ref, m_ref, o_ref, *, eps):
    y = _normed_rows(x_ref[:], w_ref[:], eps)   # [br, H]
    # the MXU accumulates in fp32 (Mosaic refuses a narrower accumulator)
    o_ref[:] = jnp.dot(
        y, m_ref[:], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm_matmul(x2d, w, wm, eps, block_rows, block_cols):
    n, h = x2d.shape
    n_out = wm.shape[1]
    out_dtype = jnp.promote_types(x2d.dtype, wm.dtype)
    return pl.pallas_call(
        functools.partial(_fused_kernel, eps=eps),
        grid=(n // block_rows, n_out // block_cols),
        in_specs=[
            pl.BlockSpec((block_rows, h), lambda i, j: (i, 0)),
            pl.BlockSpec((1, h), lambda i, j: (0, 0)),
            pl.BlockSpec((h, block_cols), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n_out), out_dtype),
        interpret=autotune.interpret_mode(),
        name="rms_norm_matmul",
    )(x2d, w.reshape(1, h), wm)


def _composed_2d(x2d, w, wm, eps):
    return jnp.dot(_normed_rows(x2d, w.reshape(1, -1), eps), wm)


def _fwd(x2d, w, wm, eps, block_rows, block_cols):
    return (
        _norm_matmul(x2d, w, wm, eps, block_rows, block_cols),
        (x2d, w, wm),
    )


def _bwd(eps, block_rows, block_cols, res, g):
    x2d, w, wm = res
    _, vjp = jax.vjp(
        lambda xv, wv, mv: _composed_2d(xv, wv, mv, eps), x2d, w, wm
    )
    return vjp(g)


_norm_matmul.defvjp(_fwd, _bwd)


def _resolve_blocks(rows, n_out, block_rows, block_cols):
    if block_rows is None or block_cols is None:
        cands = autotune.norm_matmul_candidates(rows, n_out)
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


def rms_norm_matmul(x, w, wm, eps=1e-6, block_rows=None, block_cols=None):
    """``rms_norm(x, w) @ wm`` in one kernel. x: [..., H]; w: [H] norm
    weight; wm: [H, N] matmul weight (paddle Linear layout). Returns
    [..., N]."""
    shape = x.shape
    h = int(shape[-1])
    x2d = x.reshape(-1, h)
    rows, n_out = int(x2d.shape[0]), int(wm.shape[1])
    br, bc = _resolve_blocks(rows, n_out, block_rows, block_cols)
    out = _norm_matmul(x2d, w, wm, float(eps), br, bc)
    return out.reshape(tuple(shape[:-1]) + (n_out,))


def rms_norm_matmul_composed(x, w, wm, eps=1e-6):
    """Composed reference (plain jnp, XLA-fused): normalize then matmul
    — op-for-op the math of the fused kernel, without the fusion. The
    parity tests pin the two bit-exact; the fused backward runs through
    this function's VJP."""
    shape = x.shape
    x2d = x.reshape(-1, int(shape[-1]))
    out = _composed_2d(x2d, w, wm, float(eps))
    return out.reshape(tuple(shape[:-1]) + (int(wm.shape[1]),))


def head_fusion_select(rows, hidden, n_out):
    """Tune-cache OPT-IN selection for the norm+matmul head: the fused
    config when a measured entry exists for this exact shape on this
    device, else None (call sites keep the unfused path —
    byte-identical to the pre-autotuner behavior)."""
    sig = autotune.norm_matmul_sig(rows, hidden, n_out)
    entry = autotune.lookup_entry("rms_norm_matmul", sig)
    if entry is None:
        return None
    cfg = dict(entry["config"])
    if not autotune.norm_matmul_config_legal(rows, n_out, cfg):
        autotune.note_fallback(
            "rms_norm_matmul", sig, "stale-config",
            detail=f"cached {cfg} illegal for ({rows}, {n_out})",
        )
        return None
    if entry.get("fused_beats_composed") is False:
        # the tuner measured composed FASTER for this exact shape on
        # this device — a measured policy decision, not a fallback
        autotune.note_selection("rms_norm_matmul", "composed:measured")
        return None
    if autotune.spmd_refusal("rms_norm_matmul"):
        return None
    autotune.note_selection("rms_norm_matmul", "fused:cached")
    return cfg


def _apply_fn(xv, wv, mv, *, eps, block_rows, block_cols):
    return rms_norm_matmul(xv, wv, mv, eps=eps, block_rows=block_rows,
                           block_cols=block_cols)


def rms_norm_matmul_apply(x, w, wm, *, eps=1e-6, block_rows=None,
                          block_cols=None):
    """Tensor-level entry (grad-recording via core.dispatch) for model
    code."""
    from ..core import dispatch

    return dispatch.apply(
        "rms_norm_matmul", _apply_fn, (x, w, wm),
        {"eps": float(eps), "block_rows": block_rows,
         "block_cols": block_cols},
    )
