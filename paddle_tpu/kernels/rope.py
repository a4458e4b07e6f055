"""Fused rotary position embedding — Pallas TPU kernel.

Reference parity: phi FusedRopeKernel (paddle/phi/kernels/fusion/gpu/
fused_rope_kernel.cu — unverified, mount empty). Layout follows paddle's
fused_rotary_position_embedding: q/k are [B, S, H, D]; rotation pairs are
(x[..., :D/2], x[..., D/2:]) ("neox"/llama style). Backward is the inverse
rotation (same kernel, negated sin) via custom_vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


from . import autotune


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)  # [1, S_blk, H, D]
    cos = cos_ref[:].astype(jnp.float32)  # [1, S_blk, 1, D/2]
    sin = sin_ref[:].astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1 = x[..., :d2]
    x2 = x[..., d2:]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    o_ref[:] = jnp.concatenate([o1, o2], axis=-1).astype(o_ref.dtype)


def _seq_block(s, h, d):
    """Largest seq tile whose f32 working set (~7 temporaries of
    [sb, H, D]) stays well inside scoped VMEM: cap one temp at 2MB.
    Pallas TPU needs the last two block dims whole, so tiling is over
    (batch, seq) only."""
    cap = (512 * 1024) // (4 * h * d)
    for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if b <= max(cap, 1) and s % b == 0:
            return b
    return 1


def _rope_apply(x, cos, sin):
    b, s, h, d = x.shape
    sb = _seq_block(s, h, d)
    # cos/sin are [1, S, 1, D/2] (one table shared across the batch) or
    # [B, S, 1, D/2] (per-row position gathers — the serving engine's
    # continuous-batching decode, where every batch slot sits at its own
    # position); a shared table always reads batch row 0
    cb = cos.shape[0]
    if cb not in (1, b):
        raise ValueError(
            f"rope cos/sin batch dim must be 1 or {b}, got {cb}"
        )
    tab = (lambda i, k: (i, k, 0, 0)) if cb == b else (
        lambda i, k: (0, k, 0, 0)
    )
    out = pl.pallas_call(
        _rope_kernel,
        grid=(b, s // sb),
        in_specs=[
            pl.BlockSpec((1, sb, h, d), lambda i, k: (i, k, 0, 0)),
            pl.BlockSpec((1, sb, 1, d // 2), tab),
            pl.BlockSpec((1, sb, 1, d // 2), tab),
        ],
        out_specs=pl.BlockSpec((1, sb, h, d), lambda i, k: (i, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), x.dtype),
        interpret=autotune.interpret_mode(),
        name="rope",
    )(x, cos, sin)
    return out


@jax.custom_vjp
def rope_fused(x, cos, sin):
    """Apply rotary embedding. x [B,S,H,D]; cos/sin [1,S,1,D/2]."""
    return _rope_apply(x, cos, sin)


def _rope_fwd(x, cos, sin):
    return _rope_apply(x, cos, sin), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    # inverse rotation: rotate by -theta
    return _rope_apply(g, cos, -sin), None, None


rope_fused.defvjp(_rope_fwd, _rope_bwd)


def build_rope_cache(seq_len, head_dim, base=10000.0, dtype=jnp.float32):
    """cos/sin tables [1, S, 1, D/2] (paddle/llama convention)."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [S, D/2]
    return (
        jnp.cos(freqs)[None, :, None, :].astype(dtype),
        jnp.sin(freqs)[None, :, None, :].astype(dtype),
    )
