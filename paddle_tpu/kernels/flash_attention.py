"""Flash attention for TPU.

Reference parity: phi FlashAttnKernel (paddle/phi/kernels/gpu/
flash_attn_kernel.cu wrapping the flash-attention lib — unverified, mount
empty). On TPU the equivalent is a Pallas blockwise-softmax kernel; jax
ships a production-quality one (jax.experimental.pallas.ops.tpu.flash_attention)
which we use when shapes allow, with a composed-jnp fallback otherwise.
Layout contract matches paddle: q/k/v are [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _composed(q, k, v, *, causal, scale):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(out, 1, 2)


@functools.lru_cache(maxsize=1)
def _pallas_fa():
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention,
        )

        return flash_attention
    except Exception:
        return None


# Round-5 v5e ablation (fwd+bwd, causal, B=4 H=16 D=128 — the flagship
# head geometry; interleaved A/B medians, BENCH_NOTES for the full
# table). The round-3 "pallas always loses on time" result was an
# artifact of the kernel's DEFAULT block sizes (8x128 q-blocks); with
# blocks tuned for v5e (block_q=512, block_k_major=1024, block_k=512 —
# and the same for both backward passes) the causal kernel's
# block-skipping of upper-triangle work wins outright once S is large
# enough for the skipped half to dominate:
#   S=1024: composed 23.9ms  pallas-tuned 24.2ms   (parity, within noise)
#   S=2048: composed 29.6ms  pallas-tuned 27.7ms   (pallas)
#   S=4096: composed 30.7ms  pallas-tuned 20.1ms   (pallas, 1.5x)
#   (default blocks for reference: 10.0/23.9/78.3ms at 1024/2048/4096)
# Selection: the tuned pallas kernel for causal attention from S>=2048
# (the isolated A/B is parity at 1024, but inside the full compiled
# flagship step composed still edges it there — 64.2% vs 62.6% MFU
# measured — so the threshold sits where the win is real), and for ANY
# shape whose fp32 score matrix exceeds SCORE_BYTES_THRESHOLD (composed
# materializes O(B*H*S^2) scores; flash is O(S)). Non-causal below the
# threshold stays composed — there is no triangle to skip and XLA's
# fused attention is at parity or better there.
#
# Which BLOCK SIZES the pallas path uses is now a tune-cache lookup
# (kernels/autotune.py): a measured entry for (shape, device) wins;
# otherwise the seeded v5e triple below (clamped for short seqs); and
# when the seed is not legal for the shape, the divisibility-aware
# candidate generator supplies a legal config instead of silently
# dropping to composed.

# The 2 GiB fp32-score-matrix threshold. ONE home (exported from
# kernels/__init__.py) — BENCH_NOTES prose and the selection logic both
# refer to this constant.
SCORE_BYTES_THRESHOLD = 2 << 30
_PALLAS_CAUSAL_MIN_SEQ = 2048

# the hand-measured v5e optimum (BENCH_NOTES r5) — the seeded default
# every shape gets until a tune-cache entry supersedes it
SEED_BLOCKS = {"block_q": 512, "block_k_major": 1024, "block_k": 512}


def _seed_config(sq, sk):
    """The seeded v5e triple, clamped for short sequences."""
    return {
        "block_q": min(SEED_BLOCKS["block_q"], sq),
        "block_k_major": min(SEED_BLOCKS["block_k_major"], sk),
        "block_k": min(SEED_BLOCKS["block_k"], sk),
    }


def _resolve_config(sq, sk, b=None, h=None, d=None, causal=True):
    """Block config for (sq, sk) and where it came from:
    ``(config, source, fused_wins)`` with source one of "cached"
    (tune-cache entry for the full shape signature), "seed" (the v5e
    default, clamped), "generated" (divisibility-aware candidate —
    legal but unmeasured), or ``(None, "none", None)`` when no legal
    config exists (sq/sk lack an MXU-friendly divisor).
    ``fused_wins`` is the tuner's measured fused-vs-composed verdict
    for a cached entry (None when absent/unmeasured — the seeded v5e
    entries are hand-validated wins)."""
    from . import autotune

    if b is not None and h is not None and d is not None:
        sig = autotune.flash_sig(b, sq, sk, h, d, causal)
        entry = autotune.lookup_entry("flash_attention", sig)
        if entry is not None:
            cached = dict(entry["config"])
            if autotune.flash_config_legal(sq, sk, cached):
                return cached, "cached", entry.get("fused_beats_composed")
            # a stale/illegal cached entry must be as visible here as it
            # is for the fusion kernels (metric + one-shot warning)
            autotune.note_fallback(
                "flash_attention", sig, "stale-config",
                detail=f"cached {cached} illegal for sq={sq} sk={sk}",
            )
    seed = _seed_config(sq, sk)
    if autotune.flash_config_legal(sq, sk, seed):
        return seed, "seed", None
    cands = autotune.flash_block_candidates(sq, sk)
    if cands:
        return cands[0], "generated", None
    return None, "none", None


def _tuned_block_sizes(sq, sk, b=None, h=None, d=None, causal=True,
                       config=None):
    """BlockSizes for the stock kernel: the tune-cache entry when one
    exists for the full (b, sq, sk, h, d, causal) signature, else the
    seeded v5e triple (clamped), else a generated legal config."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
    )

    cfg = config or _resolve_config(sq, sk, b=b, h=h, d=d,
                                    causal=causal)[0]  # (cfg, src, wins)
    if cfg is None:
        cfg = _seed_config(sq, sk)  # caller should have checked legality
    bq, bkm, bk = cfg["block_q"], cfg["block_k_major"], cfg["block_k"]
    return BlockSizes(
        block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bkm, block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bkm, block_k_dq=bk, block_q_dq=bq,
    )


def _select(q, k, v, causal):
    """Full selection decision: ``(use_pallas, config, reason)``.

    ``reason`` explains composed picks: policy reasons (the composed
    path is genuinely preferred) are silent; capability fallbacks (the
    pallas path is WANTED but cannot run) publish a fallback metric, a
    one-shot warning, and a flight-recorder event via
    ``autotune.note_fallback`` — a non-divisible long-context shape no
    longer loses its 1.5x win silently."""
    from . import autotune

    b, sq, h, d = (int(s) for s in q.shape)
    sk = int(k.shape[1])
    if all(dev.platform == "cpu" for dev in jax.devices()):
        return False, None, "policy:cpu"
    score_bytes = 4 * b * h * sq * sk  # fp32 softmax intermediate
    wanted = (
        # sq == sk required: for cross-length causal attention the
        # pallas kernel's top-left-aligned causal mask disagrees with
        # composed's bottom-right-aligned one (tril k=sk-sq)
        (causal and sq == sk and sk >= _PALLAS_CAUSAL_MIN_SEQ)
        or (not causal and score_bytes > SCORE_BYTES_THRESHOLD)
        or (causal and sq == sk and score_bytes > SCORE_BYTES_THRESHOLD)
    )
    if not wanted:
        if causal and sq != sk and (
                sk >= _PALLAS_CAUSAL_MIN_SEQ
                or score_bytes > SCORE_BYTES_THRESHOLD):
            # cross-length causal is a semantic exclusion, but at these
            # sizes the composed path is paying the full O(S^2) bill —
            # surface it (it is the paged/decode shape to fix next)
            return False, None, "policy:cross-length-causal"
        return False, None, "policy:below-threshold"
    sig = autotune.flash_sig(b, sq, sk, h, d, causal)
    if autotune.spmd_refusal("flash_attention"):
        return False, None, "fallback:unpartitionable"
    if _pallas_fa() is None:
        autotune.note_fallback("flash_attention", sig,
                               "kernel-unavailable")
        return False, None, "fallback:kernel-unavailable"
    if int(v.shape[1]) != sk:
        autotune.note_fallback("flash_attention", sig, "kv-length-mismatch")
        return False, None, "fallback:kv-length-mismatch"
    if d not in (64, 128, 256):
        autotune.note_fallback("flash_attention", sig, "head-dim",
                               detail=f"d={d} not in (64, 128, 256)")
        return False, None, "fallback:head-dim"
    cfg, source, fused_wins = _resolve_config(sq, sk, b=b, h=h, d=d,
                                              causal=causal)
    if cfg is None:
        autotune.note_fallback(
            "flash_attention", sig, "indivisible",
            detail=f"sq={sq} sk={sk} have no legal block config",
        )
        return False, None, "fallback:indivisible"
    if (source == "cached" and fused_wins is False
            and score_bytes <= SCORE_BYTES_THRESHOLD):
        # the tuner measured composed FASTER than the best pallas
        # candidate for this exact shape — honor the measurement in the
        # time regime (a measured policy decision, not a fallback). In
        # the memory regime pallas still wins by not materializing the
        # O(S^2) scores, whatever the isolated timing said.
        return False, None, "policy:measured-composed-wins"
    if source == "generated" and score_bytes <= SCORE_BYTES_THRESHOLD:
        # a generated config is legal but UNMEASURED, and its blocks are
        # necessarily smaller than the seed's (the seed was illegal) —
        # BENCH_NOTES measured small/default blocks up to 2.5x slower
        # than composed, so in the time-win regime composed is the safe
        # choice until the tuner measures this shape. In the memory
        # regime (score matrix > SCORE_BYTES_THRESHOLD) any legal
        # pallas config beats materializing the O(S^2) scores.
        autotune.note_fallback(
            "flash_attention", sig, "untuned-config",
            detail=f"generated {cfg} is unmeasured; composed kept",
        )
        return False, None, "fallback:untuned-config"
    return True, cfg, f"pallas:{source}"


def _pallas_ok(q, k, v, causal):
    return _select(q, k, v, causal)[0]


def flash_attention_fwd(q, k, v, causal=False, scale=None):
    """q/k/v: [B, S, H, D] -> [B, S, H, D].

    q and k/v may arrive in different dtypes (bf16 KV caches from the
    serving pool / ``generate(cache_dtype=...)``, or fp32 caches under
    a bf16-activation model): align everything to the PROMOTED dtype —
    always widening, never rounding a wider cache down — so the Pallas
    kernel sees uniform operands and the composed path gets exactly the
    promotion XLA would insert."""
    ct = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype), v.dtype)
    if q.dtype != ct:
        q = q.astype(ct)
    if k.dtype != ct:
        k = k.astype(ct)
    if v.dtype != ct:
        v = v.astype(ct)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    use_pallas, cfg, reason = _select(q, k, v, causal)
    from . import autotune

    # the full reason is the path label ("pallas:seed", "policy:
    # cross-length-causal", "fallback:indivisible", ...): composed picks
    # stay distinguishable by WHY — e.g. the cross-length causal decode
    # shape paying the O(S^2) bill is its own series, not an anonymous
    # "composed"
    autotune.note_selection("flash_attention", reason)
    if use_pallas:
        fa = _pallas_fa()
        # pallas kernel layout: [B, H, S, D]
        out = fa(
            jnp.swapaxes(q, 1, 2),
            jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2),
            causal=causal,
            sm_scale=scale,
            block_sizes=_tuned_block_sizes(
                int(q.shape[1]), int(k.shape[1]), config=cfg
            ),
        )
        return jnp.swapaxes(out, 1, 2)
    return _composed(q, k, v, causal=causal, scale=scale)
