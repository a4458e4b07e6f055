"""Fused RMSNorm — Pallas TPU kernel with custom VJP.

Reference parity: phi fused RmsNormKernel (paddle/phi/kernels/fusion/gpu/
fused_layernorm_kernel.cu family — unverified, mount empty). One VMEM pass
per row block: mean-of-squares, rsqrt, scale — keeping the activation in
VMEM instead of three HBM round trips. Backward fuses dx and accumulates dw
across row blocks in a resident output block.

Falls back to pallas interpret mode off-TPU (CI) — same code path, host
execution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


from . import autotune


# Mosaic gives a kernel 16 MiB of scoped VMEM on a v5e unless told
# otherwise; the row block is sized to stay inside three quarters of it.
_VMEM_BUDGET = 12 << 20


def _block_rows(n, h, itemsize, n_io, n_tmp):
    """Rows per grid step: the largest rung whose VMEM footprint fits
    the budget — ``n_io`` double-buffered ``[rows, h]`` activation
    blocks of ``itemsize`` bytes plus ``n_tmp`` fp32 ``[rows, h]``
    temporaries the body keeps live. The v5e compiler's own allocation
    reports at hidden 4096 put the temporaries at 1 (fwd) and 3.5 (bwd)
    per element; the rest of the elementwise chain fuses.

    The rung need not divide ``n``: the grid is ``cdiv(n, rows)`` and
    the last block may hang over the end (its extra rows are never
    written back; the backward masks them out of ``dw``). A block's
    row count must be a multiple of 8 or the whole axis, so a prompt of
    338 tokens cannot be tiled by its divisors (2 x 169)."""
    if n <= 8:
        return n  # a whole-axis block is legal at any row count
    cap = max(_VMEM_BUDGET // (h * (2 * n_io * itemsize + 4 * n_tmp)), 8)
    return next(b for b in (256, 128, 64, 32, 16, 8) if b <= min(cap, n))


# ------------------------------------------------------------------ forward


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = ((x * rstd) * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _rms_fwd(x2d, w, eps):
    n, h = x2d.shape
    br = _block_rows(n, h, x2d.dtype.itemsize, n_io=2, n_tmp=1)
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(pl.cdiv(n, br),),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=autotune.interpret_mode(),
        name="rms_norm_fwd",
    )(x2d, w.reshape(1, h))
    return y, rstd


# ----------------------------------------------------------------- backward


def _bwd_kernel(x_ref, w_ref, g_ref, rstd_ref, dx_ref, dw_ref, *, n_rows):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    gw = g * w
    # dx = rstd * gw - x * rstd^3 * mean(gw * x)
    m = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx = rstd * gw - x * (rstd * rstd * rstd) * m
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dw accumulates across row blocks into the single resident block
    gx = g * (x * rstd)
    br = x.shape[0]
    if n_rows % br:
        # the last block hangs over the end: what it read there is not
        # data (a select, not a multiply — it may be NaN)
        row = pl.program_id(0) * br + jax.lax.broadcasted_iota(
            jnp.int32, (br, 1), 0)
        gx = jnp.where(row < n_rows, gx, 0.0)
    part = jnp.sum(gx, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = part

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dw_ref[:] += part


def _rms_bwd(x2d, w, g2d, rstd):
    n, h = x2d.shape
    br = _block_rows(n, h, x2d.dtype.itemsize, n_io=3, n_tmp=4)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, n_rows=n),
        grid=(pl.cdiv(n, br),),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=autotune.interpret_mode(),
        name="rms_norm_bwd",
    )(x2d, w.reshape(1, h), g2d, rstd)
    return dx, dw.reshape(h)


# -------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_fused(x, w, eps=1e-6):
    """x: [..., H] float; w: [H]. Returns normalized*w, same dtype as x."""
    shape = x.shape
    y, _ = _rms_fwd(x.reshape(-1, shape[-1]), w, eps)
    return y.reshape(shape)


def _vjp_fwd(x, w, eps):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, rstd = _rms_fwd(x2d, w, eps)
    return y.reshape(shape), (x2d, w, rstd, shape)


def _vjp_bwd(eps, res, g):
    x2d, w, rstd, shape = res
    dx, dw = _rms_bwd(x2d, w, g.reshape(x2d.shape).astype(x2d.dtype), rstd)
    return dx.reshape(shape), dw.astype(w.dtype)


rms_norm_fused.defvjp(_vjp_fwd, _vjp_bwd)
