"""Paged decode attention — Pallas TPU kernel over a block/page KV pool.

The serving engine's paged decode keeps K/V in a page arena
(``[num_pages, page_size, kvH, D]`` per layer) and addresses each
request's cache through a per-row page table (``[B, pages]`` int32,
page id 0 = the reserved garbage page for unallocated tail entries).
The composed path materializes the gathered cache
(``k_pages[page_table]`` -> ``[B, pages * page_size, kvH, D]``) in HBM
every decode step; this kernel gathers page blocks straight into VMEM
through a scalar-prefetched page table (the classic paged-attention
structure: the table is available before the kernel body runs, so the
BlockSpec index_map can pull the right page per grid step).

Shape contract: q is ``[B, 1, H, D]`` (one decode token per row),
k_pages/v_pages ``[N, page_size, kvH, D]``, page_table ``[B, P]``
int32, pos ``[B]`` int32 (tokens already cached per row; the row
attends cache slots ``[0, pos]`` inclusive — the slot written this
step included).

Exact-softmax discipline (the PR 6 fusion-kernel contract): the kernel
sweeps a row's page table twice. Sweep 0 assembles the FULL score row
in VMEM scratch page by page and softmaxes it ONCE in fp32 (never
online-rescaled); sweep 1 accumulates the value sum page by page in
table order. Scores and values are multiply-reduces on the VPU over the
page block as the arena stores it (``[page_size, kvH, D]``, slot-major):
the chip's compiler takes that block only with the whole ``kvH`` axis
(or a multiple of 8 of it) in it, and a per-head MXU matmul would need
the page relaid head-major first. Only the full-row score scratch
(``[group, S_virtual, block_kvh]`` fp32, 1 MiB per group at
``S_virtual`` 2048) scales with the context. Two reference functions:

- :func:`paged_attention_reference` mirrors the kernel's blocked math
  op-for-op (pure jnp, sharing the kernel's own score/value/softmax
  helpers) and is pinned EXACTLY EQUAL to the kernel under jit in CI
  (the PR 6 parity discipline; the kernel is also invariant in its
  ``block_kvh`` knob).
- :func:`paged_attention_composed` is the gather+SDPA formulation the
  serving engine's DEFAULT paged path runs (``_sdpa_ref``, or under
  GQA its grouped form ``_sdpa_grouped_ref`` that reads each KV head
  once; the slab engine decodes through the same two — that identity
  is what keeps default paged token streams exact-equal to
  ``net.generate``).
  Kernel vs composed agree to float rounding (a different reduction
  order; the parity test bounds it at fp32 epsilon), which is why
  kernel activation stays a measured, opt-in decision rather than a
  default.

Selection is tune-cache OPT-IN (:func:`paged_attention_select`): with
no measured entry for the exact (shape, device) signature the engine
keeps the composed gather path byte-identical; ``tools/kernel_tune.py``
measures and records entries. The tunable is ``block_kvh`` — KV heads
per grid step (``autotune.paged_attention_candidates``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune


def gather_pages(pages, page_table):
    """``[N, ps, kvH, D]`` arena + ``[B, P]`` table ->
    ``[B, P * ps, kvH, D]`` logical cache (HBM-materializing composed
    gather; the kernel's whole reason to exist is skipping this copy)."""
    b, p = page_table.shape
    n, ps, kvh, d = pages.shape
    return pages[page_table].reshape(b, p * ps, kvh, d)


def gather_pages_dense(pages, page_table, dtype):
    """Composed gather for either arena flavor. Plain arrays: exactly
    :func:`gather_pages` (no cast — the bf16 path stays byte-identical;
    attention upcasts at the matmul). Quantized arenas: gather the int8
    values and their scales, then dequantize-on-gather to the compute
    ``dtype`` — the int8 bytes are what crossed HBM."""
    from ..quantization.kv import dequantize_kv, is_quantized

    if not is_quantized(pages):
        return gather_pages(pages, page_table)
    b, p = page_table.shape
    n, ps, kvh, d = pages.q.shape
    q = pages.q[page_table].reshape(b, p * ps, kvh, d)
    s = pages.scale[page_table].reshape(b, p * ps, kvh)
    return dequantize_kv(q, s, dtype)  # tpu-lint: quant


def _softmax_rows(s, axis=-1):
    """fp32 softmax along ``axis``, op-for-op ``jax.nn.softmax``
    (max-subtract, exp, sum-normalize) — masked -inf entries contribute
    exactly 0."""
    m = jnp.max(s, axis=axis, keepdims=True)
    p = jnp.exp(s - m)
    return p / jnp.sum(p, axis=axis, keepdims=True)


def paged_attention_composed(q, k_pages, v_pages, page_table, pos,
                             scale=None):
    """Composed reference: gather the paged cache and attend through the
    very bodies the engine's default paths run
    (``nn.functional.attention``: ``_sdpa_ref`` under MHA,
    ``_sdpa_grouped_ref`` — the grouped contraction over the
    un-repeated KV heads — under GQA), so the paged engine's default
    path and the slab engine round identically.

    q ``[B, 1, H, D]``; returns ``[B, 1, H, D]`` in q's dtype.
    Quantized (int8) arenas dequantize-on-gather to q's dtype first —
    the op order the engine's default int8 paged path runs."""
    from ..nn.functional.attention import _sdpa_grouped_ref, _sdpa_ref

    h, d = int(q.shape[2]), int(q.shape[3])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kk = gather_pages_dense(k_pages, page_table, q.dtype)
    vv = gather_pages_dense(v_pages, page_table, q.dtype)
    valid = jnp.arange(int(kk.shape[1]))[None, None, None, :] \
        <= pos[:, None, None, None]
    mask = jnp.where(valid, 0.0, -jnp.inf)
    if int(kk.shape[2]) != h:
        return _sdpa_grouped_ref(q, kk, vv, mask, scale=scale)
    return _sdpa_ref(q, kk, vv, mask, causal=False, scale=scale,
                     dropout_p=0.0, key=None)


def _page_scores(qg, k, scale):
    """One page's score block for one query head per KV head: ``qg``
    ``[kvh, D]`` against ``k`` ``[ps, kvh, D]`` (both fp32) ->
    ``[ps, kvh]``. A multiply and a reduce over D on the VPU — the page
    arrives slot-major, so there is no per-head matrix to hand the
    MXU without relaying the page out. Shared by the kernel body and
    the blocked reference so the two can never round apart."""
    return jnp.sum(k * qg[None], axis=-1) * scale


def _page_values(pg, v):
    """One page's contribution to the output: probabilities ``pg``
    ``[ps, kvh]`` over values ``v`` ``[ps, kvh, D]`` -> ``[kvh, D]``
    (shared by kernel and reference like :func:`_page_scores`)."""
    return jnp.sum(pg[:, :, None] * v, axis=0)


def _masked_probs(s, pos, out_dtype):
    """``[group, S_virt, kvh]`` scores -> probabilities: position mask,
    ONE fp32 softmax over the whole virtual row, rounded through the
    activation dtype exactly as the composed path rounds them."""
    slots = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    sm = s + jnp.where(slots <= pos, 0.0, -jnp.inf)
    return _softmax_rows(sm, axis=1).astype(out_dtype).astype(jnp.float32)


def paged_attention_reference(q, k_pages, v_pages, page_table, pos,
                              scale=None):
    """Pure-jnp mirror of the kernel's blocked math (per row: per-page
    score blocks assembled into the full virtual row, ONE softmax, then
    the value sum accumulated page by page in table order). Pinned
    bit-identical to :func:`paged_attention_fused` in CI. Loop-based —
    a verification reference, not a serving path. Quantized arenas
    dequantize each page block to fp32 (value * scale) exactly as the
    kernel does in VMEM, so the bit-exact pin covers the int8 flavor
    too."""
    from ..quantization.kv import is_quantized

    b, sq, h, d = (int(x) for x in q.shape)

    def _page(pages_arr, bi, p):
        if is_quantized(pages_arr):
            return (
                pages_arr.q[page_table[bi, p]].astype(jnp.float32)
                * pages_arr.scale[page_table[bi, p]][..., None]
            )  # tpu-lint: quant
        return pages_arr[page_table[bi, p]].astype(jnp.float32)

    k_arr = k_pages.q if is_quantized(k_pages) else k_pages
    ps, kvh = int(k_arr.shape[1]), int(k_arr.shape[2])
    pages = int(page_table.shape[1])
    group = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    rows = []
    for bi in range(b):
        # query heads group-major: qg[g, j] is head j * group + g
        qg = jnp.swapaxes(
            q[bi, 0].reshape(kvh, group, d), 0, 1).astype(jnp.float32)
        s = jnp.stack([
            jnp.concatenate([
                _page_scores(qg[g], _page(k_pages, bi, p), scale)
                for p in range(pages)
            ], axis=0)
            for g in range(group)
        ])                                              # [group, S, kvh]
        prob = _masked_probs(s, pos[bi], q.dtype)
        acc = [jnp.zeros((kvh, d), jnp.float32)] * group
        for p in range(pages):
            vpage = _page(v_pages, bi, p)
            for g in range(group):
                acc[g] = acc[g] + _page_values(
                    prob[g, p * ps:(p + 1) * ps], vpage)
        rows.append(jnp.swapaxes(jnp.stack(acc), 0, 1).reshape(h, d))
    return jnp.stack(rows)[:, None].astype(q.dtype)


def _paged_body(pos_ref, q_ref, k, v, o_ref, s_scratch, acc_scratch, *,
                scale, page_size, pages, out_dtype):
    """The SHARED kernel body both arena flavors run after their load
    epilogue. Two sweeps over the row's page table (grid axis 2): sweep
    0 writes page p's score block into the full-row scratch and, on the
    last page, turns the row into probabilities in place; sweep 1 adds
    page p's value contribution to the accumulator and emits on the
    last page. ``k``/``v`` arrive as fp32 ``[ps, bkvh, D]`` — already
    dequantized by the caller — so the masking/softmax/emit math has
    exactly ONE home and the two flavors can never round apart. Only
    the sweep's own operand is a fresh page: the index maps park the
    other one, so each page crosses HBM once per sweep.

    q_ref ``[1, 1, group, bkvh, D]``."""
    b = pl.program_id(0)
    sweep = pl.program_id(2)
    p = pl.program_id(3)
    group = q_ref.shape[2]
    rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)

    @pl.when(sweep == 0)
    def _scores():
        for g in range(group):
            s_scratch[g, rows, :] = _page_scores(
                q_ref[0, 0, g].astype(jnp.float32), k, scale)

        @pl.when(p == pages - 1)
        def _softmax():
            s_scratch[...] = _masked_probs(s_scratch[...], pos_ref[b],
                                           out_dtype)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

    @pl.when(sweep == 1)
    def _values():
        for g in range(group):
            acc_scratch[g] += _page_values(s_scratch[g, rows, :], v)

        @pl.when(p == pages - 1)
        def _emit():
            o_ref[0, 0] = acc_scratch[...].astype(o_ref.dtype)


def _paged_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  s_scratch, acc_scratch, **kw):
    """Float-arena flavor: load epilogue is a plain fp32 upcast of the
    table-indexed page block; everything else is :func:`_paged_body`.

    k_ref/v_ref ``[1, ps, bkvh, D]`` — one table-indexed page block."""
    _paged_body(
        pos_ref, q_ref,
        k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
        o_ref, s_scratch, acc_scratch, **kw,
    )


def _paged_kernel_quant(table_ref, pos_ref, q_ref, k_ref, ks_ref, v_ref,
                        vs_ref, o_ref, s_scratch, acc_scratch, **kw):
    """Int8-arena flavor: the page block arrives as int8 values +
    per-(slot, kv-head) fp32 scales and the load epilogue dequantizes
    in VMEM (value * scale — the exact op order the blocked reference
    mirrors), so only the narrow bytes ever cross HBM. Everything past
    the load is the shared :func:`_paged_body`."""
    # dequant-on-gather, in VMEM: [ps, bkvh, D] fp32  # tpu-lint: quant
    k = k_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0][..., None]
    _paged_body(pos_ref, q_ref, k, v, o_ref, s_scratch, acc_scratch,
                **kw)


def paged_attention_fused(q, k_pages, v_pages, page_table, pos,
                          scale=None, block_kvh=None):
    """Pallas paged decode attention. Shapes per the module docstring;
    ``block_kvh`` KV heads are processed per grid step (tuned knob;
    default all of them). The chip's compiler takes a page block only
    when ``block_kvh`` is the whole KV-head axis or a multiple of 8
    (``autotune.paged_attention_config_legal``); interpret mode takes
    any divisor. ``k_pages``/``v_pages`` may be int8 ``QuantizedKV``
    arenas — the kernel then streams int8 pages + scales and
    dequantizes in VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    from ..quantization.kv import is_quantized

    quant = is_quantized(k_pages)
    if quant != is_quantized(v_pages):
        raise ValueError("k_pages and v_pages must share quantization")
    k_arr = k_pages.q if quant else k_pages
    b, sq, h, d = (int(x) for x in q.shape)
    if sq != 1:
        raise ValueError(
            f"paged attention is the decode step: one token per row "
            f"(q [B, 1, H, D]), got S={sq}"
        )
    n, ps, kvh, dk = (int(x) for x in k_arr.shape)
    if dk != d:
        raise ValueError(f"head_dim mismatch: q D={d}, pages D={dk}")
    if h % kvh:
        raise ValueError(f"H={h} not a multiple of kvH={kvh}")
    bkvh = kvh if block_kvh is None else int(block_kvh)
    if kvh % bkvh:
        raise ValueError(f"block_kvh={block_kvh} does not divide "
                         f"kvH={kvh}")
    pages = int(page_table.shape[1])
    group = h // kvh
    nblk = kvh // bkvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s_virt = pages * ps
    # query heads group-major inside a kv-head block, so a query block's
    # last two dims line up with a page block's: [B, nblk, group, bkvh, D]
    qh = q.reshape(b, nblk, bkvh, group, d).swapaxes(2, 3)
    table = page_table.astype(jnp.int32)
    posv = pos.astype(jnp.int32)
    last = pages - 1

    # sweep 0 walks K and parks V on the row's first page; sweep 1 walks
    # V and leaves K on its last page. A block whose index did not
    # change is not fetched again.
    def k_page(i, j, sw, p, tbl, ps_):
        return tbl[i, p * (1 - sw) + last * sw]

    def v_page(i, j, sw, p, tbl, ps_):
        return tbl[i, p * sw]

    def page_spec(page):
        return pl.BlockSpec(
            (1, ps, bkvh, d), lambda *a: (page(*a), 0, a[1], 0))

    def scale_spec(page):
        return pl.BlockSpec(
            (1, ps, bkvh), lambda *a: (page(*a), 0, a[1]))

    q_spec = pl.BlockSpec((1, 1, group, bkvh, d),
                          lambda i, j, sw, p, tbl, ps_: (i, j, 0, 0, 0))
    if quant:
        in_specs = [q_spec, page_spec(k_page), scale_spec(k_page),
                    page_spec(v_page), scale_spec(v_page)]
        operands = (table, posv, qh, k_pages.q, k_pages.scale,
                    v_pages.q, v_pages.scale)
        kernel = _paged_kernel_quant
    else:
        in_specs = [q_spec, page_spec(k_page), page_spec(v_page)]
        operands = (table, posv, qh, k_pages, v_pages)
        kernel = _paged_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # (page_table, pos)
        grid=(b, nblk, 2, pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, group, bkvh, d),
            lambda i, j, sw, p, tbl, ps_: (i, j, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, s_virt, bkvh), jnp.float32),
            pltpu.VMEM((group, bkvh, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernel, scale=float(scale), page_size=ps, pages=pages,
            out_dtype=q.dtype,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nblk, group, bkvh, d),
                                       q.dtype),
        interpret=autotune.interpret_mode(),
        name="paged_attention",
    )(*operands)
    # [B, nblk, group, bkvh, D] -> [B, 1, H, D] (head = kv-head-major)
    return out.swapaxes(2, 3).reshape(b, 1, h, d)


def paged_attention_select(b, pages, page_size, h, kvh, d,
                           quantized=False):
    """Tune-cache OPT-IN selection: the kernel's config when a measured
    entry exists for this exact shape on this device, else None (the
    engine keeps the composed gather path byte-identical). Stale cached
    configs are counted, one-shot-warned fallbacks; a measured
    composed-wins verdict is honored as a policy decision. Int8 arenas
    tune under their own signature (``..._q8``) — the int8 kernel's
    bandwidth/compute profile is different hardware behavior, so a bf16
    measurement must never activate the quantized kernel untested."""
    sig = autotune.paged_attention_sig(b, pages, page_size, h, kvh, d,
                                       quant=quantized)
    entry = autotune.lookup_entry("paged_attention", sig)
    if entry is None:
        return None
    cfg = dict(entry["config"])
    if not autotune.paged_attention_config_legal(kvh, cfg, quantized):
        autotune.note_fallback("paged_attention", sig, "stale-config",
                               detail=f"cached {cfg} illegal for "
                                      f"kvH={kvh}")
        return None
    if entry.get("fused_beats_composed") is False:
        autotune.note_selection("paged_attention", "composed:measured")
        return None
    if autotune.spmd_refusal("paged_attention"):
        return None
    autotune.note_selection("paged_attention", "fused:cached")
    return cfg


def _apply_fn(qv, kv, vv, tbl, posv, *, scale, block_kvh):
    return paged_attention_fused(qv, kv, vv, tbl, posv, scale=scale,
                                 block_kvh=block_kvh)


def paged_attention_apply(q, k_pages, v_pages, page_table, pos, *,
                          config, scale=None):
    """Tensor-level entry for model code (decode is a no-grad path, so
    no VJP is registered — ``nondiff`` keeps the tape clean)."""
    from ..core import dispatch

    return dispatch.apply(
        "paged_attention", _apply_fn,
        (q, k_pages, v_pages, page_table, pos),
        {"scale": scale, "block_kvh": int(config["block_kvh"])},
        nondiff=True,
    )


