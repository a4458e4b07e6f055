"""Fused rotary-embedding + attention — Pallas TPU kernel.

The unfused train path runs THREE passes over q/k: the rope kernel
writes a rotated copy of q and of k back to HBM (kernels/rope.py), then
attention reads both again. This kernel applies the rotation inside the
attention kernel's q/k load — the rotated tensors never exist in HBM,
and the per-block score tile stays in VMEM (composed attention
materializes the full O(B*H*S^2) score tensor).

Shape contract: q/k/v are [B, S, H, D] (paddle layout), cos/sin are the
half-dim rope tables ([1, S, 1, D/2] as built by
``kernels.rope.build_rope_cache``, or plain [S, D/2]). Self-attention
only (q and k share one sequence length and one position table) — the
training/prefill shape. Per (batch, head, q-block) grid step the kernel
rotates its q rows with their table rows, rotates + scores the full k,
and softmaxes in fp32; block_q is the tuned knob
(``autotune.rope_attention_candidates``).

Backward runs through the composed reference (``custom_vjp`` whose bwd
is the VJP of :func:`rope_attention_composed` — mathematically the same
function), so fwd+bwd training steps can select the fused forward
without a hand-written backward kernel.

Selection is tune-cache OPT-IN (:func:`rope_attention_select`): with no
cache entry for the exact (shape, device) signature, call sites keep
today's unfused path byte-identical; ``bench.py --tune`` /
``tools/kernel_tune.py`` measure and record entries.

Falls back to pallas interpret mode off-TPU (CI) — same code path, host
execution.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


from . import autotune


def _table_2d(t):
    """Accept [1, S, 1, D/2] (build_rope_cache) or [S, D/2]; return
    [S, D/2] jnp array."""
    v = t.value if hasattr(t, "value") else jnp.asarray(t)
    if v.ndim == 4:
        v = v.reshape(v.shape[1], v.shape[3])
    if v.ndim != 2:
        raise ValueError(
            f"rope table must be [1,S,1,D/2] or [S,D/2], got {v.shape}"
        )
    return v


def _rotate(x, cos, sin):
    """Neox-style rotation, fp32 in fp32 out; cos/sin broadcast over
    leading dims. Must stay op-for-op identical between the kernel body
    and the composed reference (bit-exact parity is pinned in CI)."""
    d2 = x.shape[-1] // 2
    x1 = x[..., :d2]
    x2 = x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attn_rows(s, *, causal, row0, scale):
    """Score rows -> attention weights, fp32; shared op order with the
    composed reference. ``row0``: global index of the first query row
    (for the causal mask)."""
    s = s * scale
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                               s.ndim - 2)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
        s = jnp.where(cols <= rows, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _fused_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, *,
                  scale, causal, block_q):
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)      # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)      # [S, D]
    v = v_ref[0, 0].astype(jnp.float32)      # [S, D]
    cos = cos_ref[:].astype(jnp.float32)     # [S, D/2]
    sin = sin_ref[:].astype(jnp.float32)
    row0 = pl.multiple_of(i * block_q, block_q)
    # the q rows' table slice is read off the ref: Mosaic has no
    # value-level dynamic_slice
    cos_q = cos_ref[pl.ds(row0, block_q), :].astype(jnp.float32)
    sin_q = sin_ref[pl.ds(row0, block_q), :].astype(jnp.float32)
    rq = _rotate(q, cos_q, sin_q)
    rk = _rotate(k, cos, sin)
    # contract d-with-d directly (no rk.T): the same dot_general
    # dimension numbers the composed reference's einsum lowers to, so
    # the two paths round identically (bit-exact parity pin)
    s = jax.lax.dot_general(rq, rk, (((1,), (1,)), ((), ())))
    p = _attn_rows(s, causal=causal, row0=row0, scale=scale)
    o_ref[0, 0] = jnp.dot(p, v).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rope_attention(q, k, v, cos, sin, causal, scale, block_q):
    b, s, h, d = q.shape
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q),
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda i, j, t: (i, j, t, 0)),
            pl.BlockSpec((1, 1, s, d), lambda i, j, t: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda i, j, t: (i, j, 0, 0)),
            pl.BlockSpec((s, d // 2), lambda i, j, t: (0, 0)),
            pl.BlockSpec((s, d // 2), lambda i, j, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda i, j, t: (i, j, t, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=autotune.interpret_mode(),
        name="rope_attention",
    )(qt, kt, vt, cos, sin)
    return jnp.swapaxes(out, 1, 2)


def _composed_2d_tables(q, k, v, cos, sin, causal, scale):
    # [B, S, H, D] -> [B, H, S, D], all-fp32 through the attention (the
    # fused kernel keeps everything in VMEM fp32; op order must match)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    c = cos.astype(jnp.float32)[None, None]
    si = sin.astype(jnp.float32)[None, None]
    rq = _rotate(qt, c, si)
    rk = _rotate(kt, c, si)
    p = _attn_rows(jnp.einsum("bhqd,bhkd->bhqk", rq, rk), causal=causal,
                   row0=0, scale=scale)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)


def _fwd(q, k, v, cos, sin, causal, scale, block_q):
    return (
        _rope_attention(q, k, v, cos, sin, causal, scale, block_q),
        (q, k, v, cos, sin),
    )


def _bwd(causal, scale, block_q, res, g):
    q, k, v, cos, sin = res
    _, vjp = jax.vjp(
        lambda qv, kv, vv: _composed_2d_tables(qv, kv, vv, cos, sin,
                                               causal, scale),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_rope_attention.defvjp(_fwd, _bwd)


def rope_attention_fused(q, k, v, cos, sin, causal=True, scale=None,
                         block_q=None):
    """Fused rope+attention. q/k/v: [B, S, H, D]; cos/sin: rope tables
    ([1, S, 1, D/2] or [S, D/2]). Self-attention shapes only."""
    b, s, h, d = (int(x) for x in q.shape)
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"fused rope+attention is self-attention only: q {q.shape} "
            f"k {k.shape} v {v.shape}"
        )
    cos2 = _table_2d(cos)
    sin2 = _table_2d(sin)
    if cos2.shape != (s, d // 2) or sin2.shape != (s, d // 2):
        raise ValueError(
            f"rope tables must cover [S={s}, D/2={d // 2}], got "
            f"{cos2.shape}/{sin2.shape}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if block_q is None:
        cands = autotune.rope_attention_candidates(s)
        if not cands:
            raise ValueError(f"S={s} has no legal block_q")
        block_q = cands[0]["block_q"]
    if s % int(block_q):
        raise ValueError(f"block_q={block_q} does not divide S={s}")
    return _rope_attention(q, k, v, cos2, sin2, bool(causal),
                           float(scale), int(block_q))


def rope_attention_composed(q, k, v, cos, sin, causal=True, scale=None):
    """Composed reference (plain jnp, XLA-fused): rotate q/k, then
    attention — op-for-op the math of the fused kernel, without the
    fusion. The parity tests pin the two bit-exact; the backward pass of
    :func:`rope_attention_fused` runs through this function's VJP."""
    d = int(q.shape[-1])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _composed_2d_tables(q, k, v, _table_2d(cos), _table_2d(sin),
                               bool(causal), float(scale))


def rope_attention_select(b, s, h, d):
    """Tune-cache OPT-IN selection: the fused kernel's config when a
    measured entry exists for this exact shape on this device, else
    None (call sites keep the unfused path — byte-identical to the
    pre-autotuner behavior). A cached-but-illegal (stale) config is a
    counted, one-shot-warned fallback."""
    if d % 2 or s < 8:
        return None
    sig = autotune.rope_attention_sig(b, s, h, d)
    entry = autotune.lookup_entry("rope_attention", sig)
    if entry is None:
        return None
    cfg = dict(entry["config"])
    if not autotune.rope_attention_config_legal(s, cfg):
        autotune.note_fallback("rope_attention", sig, "stale-config",
                               detail=f"cached {cfg} illegal for S={s}")
        return None
    if entry.get("fused_beats_composed") is False:
        # the tuner measured composed FASTER for this exact shape on
        # this device — a measured policy decision, not a fallback
        autotune.note_selection("rope_attention", "composed:measured")
        return None
    if autotune.spmd_refusal("rope_attention"):
        return None
    autotune.note_selection("rope_attention", "fused:cached")
    return cfg


def _apply_fn(qv, kv, vv, cv, sv, *, causal, scale, block_q):
    return rope_attention_fused(qv, kv, vv, cv, sv, causal=causal,
                                scale=scale, block_q=block_q)


def rope_attention_apply(q, k, v, cos, sin, *, causal=True, scale=None,
                         block_q=None):
    """Tensor-level entry (grad-recording via core.dispatch) for model
    code."""
    from ..core import dispatch

    return dispatch.apply(
        "rope_attention", _apply_fn, (q, k, v, cos, sin),
        {"causal": bool(causal), "scale": scale, "block_q": block_q},
    )
