"""Int8 KV-cache storage — real narrow-dtype residency for serving.

The bf16 KV caches already halved decode HBM vs fp32; this module
halves it again: K/V live as **int8 values + per-(slot, kv-head) fp32
scales** (symmetric absmax over the head dim), so a resident token
costs ``kvH * (D + 4)`` bytes instead of ``kvH * D * 2``. At flagship
head dims (D=128) that is ~1.94x fewer bytes per resident token —
compounding multiplicatively with the paged pool's per-length claims
(PR 7) at the millions-of-users concurrency ceiling.

Design contract (every call site shares these invariants):

- :class:`QuantizedKV` is a registered jax pytree, so the engines'
  flat cache lists, jit carries, scans and donation all work unchanged
  — a cache entry is simply two leaves (``q`` int8, ``scale`` fp32)
  instead of one.
- **Quantize-on-write**: every cache write path (prefill's
  ``dynamic_update_slice``, the per-row decode scatter, the paged
  (page, offset) scatter, slab/page adoption) quantizes the incoming
  tokens with :func:`quantize_kv` — per token, per kv head, absmax/127
  — so the SAME token quantizes identically in ``net.generate``, the
  slab engine and the paged engine (quantized token streams stay
  exact-equal across all three; tier-1-pinned).
- **Dequant-on-read**: the attention paths dequantize the cache view
  (the slab itself, or the table-gathered pages) to the compute dtype
  right before the masked attention; the int8 arrays are what crosses
  HBM.
- Zero-initialized storage dequantizes to exact zeros (garbage pages /
  masked columns keep contributing exact 0 through the fp32 softmax —
  the discipline that makes recycled slots safe without scrubbing).

Accuracy is a *ratcheted budget*, not a vibe: ``tests/test_serving.py``
pins the greedy-decode agreement length and the prefill-logit
max-abs-err of int8-KV decode against the bf16 baseline.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

# symmetric int8: values in [-127, 127] (the -128 code is unused so the
# scale maps absmax exactly onto the grid edge)
QMAX = 127.0
# absmax floor: an all-zero token must quantize to (0, tiny-scale) and
# dequantize to exact 0 rather than divide by zero
_EPS = 1e-8

# dtype names alloc_kv_caches accepts (the models/generation API seam
# validates against this set — see normalize_cache_dtype there)
QUANT_CACHE_DTYPES = ("int8",)


@jax.tree_util.register_pytree_node_class
class QuantizedKV:
    """One quantized cache array: ``q`` int8 ``[..., S, kvH, D]`` plus
    ``scale`` fp32 ``[..., S, kvH]`` (one scale per stored token per kv
    head). Behaves as a pytree of its two leaves, so jit carries, scan,
    flatten and donation treat it like any cache array pair."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # the pool/engine dtype checks read `.dtype` off cache arrays
    @property
    def dtype(self):
        return self.q.dtype

    @property
    def shape(self):
        return self.q.shape

    def __repr__(self):
        return (f"QuantizedKV(q={getattr(self.q, 'shape', None)}, "
                f"scale={getattr(self.scale, 'shape', None)})")


def is_quantized(cache):
    return isinstance(cache, QuantizedKV)


def alloc_quantized(shape):
    """Zeroed int8 storage + zeroed scales for a cache of logical shape
    ``[..., S, kvH, D]`` (zero scales dequantize to exact zeros)."""
    return QuantizedKV(
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape[:-1], jnp.float32),
    )


def quantize_kv(x):
    """``[..., D]`` float -> (int8 values ``[..., D]``, fp32 scales
    ``[...]``). Symmetric per-vector absmax: scale = max|x| / 127,
    rounded through bf16 before use. The rounding is what makes int8
    KV provenance-independent at the byte level: different compiled
    programs computing the same position (full prefill, chunked tail,
    S=1 decode step) may reduce ``max|x|`` in different tree shapes
    and disagree by one float32 ulp — a bf16-grid scale absorbs that,
    so a decode-written page is bitwise what re-prefilling those
    tokens writes (the serving prefix cache's decode-publish pin).
    Cost: <=2^-9 relative scale error, well under int8's own 1/127
    step."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = (jnp.maximum(absmax, _EPS) / QMAX) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -QMAX, QMAX
    ).astype(jnp.int8)  # tpu-lint: quant
    return q, scale


def dequantize_kv(q, scale, dtype):
    """int8 values + scales -> dense array in the compute ``dtype``."""
    return (
        q.astype(jnp.float32) * scale[..., None]
    ).astype(dtype)  # tpu-lint: quant


def kv_token_bytes(kv_heads, head_dim, dtype):
    """HBM bytes ONE cached token costs per K-or-V array in ``dtype``
    (int8 counts its fp32 scale overhead — the equal-HBM concurrency
    comparisons must not flatter quantized pools)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.int8:
        return kv_heads * (head_dim * dt.itemsize
                           + jnp.dtype(jnp.float32).itemsize)
    return kv_heads * head_dim * dt.itemsize


# ------------------------------------------------------------- cache writes
#
# One helper a write or read of ONE cache array, plain (cast to the
# cache's dtype, nothing else) or QuantizedKV (quantize-on-write,
# dequant-on-read), whatever its trailing shape: ``[.., kvH, D]`` or one
# ``[.., cache_dim]`` latent array. The decoders reach them through
# :func:`write_and_view` and :func:`write_and_attend_paged`, which own
# the addressing.


def write_at_pos(cache, val, pos):
    """Prefill / whole-batch decode write: ``val`` ``[B, S, kvH, D]``
    lands at positions ``[pos, pos + S)`` (scalar traced ``pos``)."""
    z = jnp.zeros((), pos.dtype)
    if is_quantized(cache):
        q, s = quantize_kv(val)
        return QuantizedKV(
            jax.lax.dynamic_update_slice(cache.q, q, (z, pos, z, z)),
            jax.lax.dynamic_update_slice(cache.scale, s, (z, pos, z)),
        )
    return jax.lax.dynamic_update_slice(
        cache, val.astype(cache.dtype), (z, pos) + (z,) * (cache.ndim - 2)
    )


def write_at_rows(cache, val, rows, cols):
    """Per-row decode write (continuous batching): ``val`` ``[B, S,
    kvH, D]`` scattered at each row's own depth (``rows``/``cols`` as
    in the slab decode path)."""
    if is_quantized(cache):
        q, s = quantize_kv(val)
        return QuantizedKV(
            cache.q.at[rows, cols].set(q),
            cache.scale.at[rows, cols].set(s),
        )
    return cache.at[rows, cols].set(val.astype(cache.dtype))


def write_paged(cache, val, page, offset):
    """Paged decode write: ``val`` ``[B, kvH, D]`` (this step's token
    per row) scattered at each row's ``(page, offset)``."""
    if is_quantized(cache):
        q, s = quantize_kv(val)
        return QuantizedKV(
            cache.q.at[page, offset].set(q),
            cache.scale.at[page, offset].set(s),
        )
    return cache.at[page, offset].set(val.astype(cache.dtype))


def read_dense(cache, dtype):
    """The composed attention read: the full cache as a dense array in
    the compute ``dtype`` (dequant-on-read for int8; pass-through for
    plain arrays — attention upcasts at the matmul as before)."""
    if is_quantized(cache):
        return dequantize_kv(cache.q, cache.scale, dtype)
    return cache


def gather_pages(pages, page_table):
    """``[N, ps, ...]`` arena + ``[B, P]`` table -> ``[B, P * ps, ...]``
    logical cache: the paged read, a copy in HBM of every page the
    table names."""
    b, p = page_table.shape
    return pages[page_table].reshape((b, p * pages.shape[1])
                                     + pages.shape[2:])


def gather_pages_dense(pages, page_table, dtype):
    """The paged read for either arena flavor. Plain arrays: exactly
    :func:`gather_pages` (no cast; attention upcasts at the matmul).
    Quantized arenas: gather the int8 values and their scales, then
    dequantize-on-gather to the compute ``dtype`` — the int8 bytes are
    what crossed HBM."""
    if not is_quantized(pages):
        return gather_pages(pages, page_table)
    return dequantize_kv(gather_pages(pages.q, page_table),
                         gather_pages(pages.scale, page_table),
                         dtype)  # tpu-lint: quant


# --------------------------------------------------------- cache addressing


def write_and_view(caches, fresh, pos, dtype=None):
    """The cache addressing of a block or a slab, for every decoder:
    write a step's new tokens into one layer's cache arrays and give
    back what attention reads. ``caches`` is the layer's tuple of arrays
    (Llama: K and V, plain or :class:`QuantizedKV`; a latent-attention
    net: one array), ``fresh`` the matching tuple of ``[B, S, ...]``
    payloads. Two modes, told apart by ``pos`` (the third, a page
    arena, is :func:`write_and_attend_paged`):

    - scalar ``pos``: a block or slab ``[B, S_max, ...]``, the tokens
      land at ``[pos, pos + S)`` of every row (prefill, a chunk at an
      offset, whole-batch decode); the view is the cache.
    - ``[B]`` ``pos``: a slab, row ``r``'s tokens land at ``[pos[r],
      pos[r] + S)`` (continuous batching); the view is the cache.

    Views come in the compute ``dtype`` where the storage is int8 and
    as stored otherwise. Returns ``(new_caches, views, cols)``,
    ``cols`` ``[B or 1, S]`` the cache column of each fresh token."""
    b, s = fresh[0].shape[:2]
    if pos.ndim == 0:
        caches = tuple(write_at_pos(c, f, pos)
                       for c, f in zip(caches, fresh))
        cols = (pos + jnp.arange(s))[None]
    else:
        rows = jnp.arange(b)[:, None]
        cols = pos[:, None] + jnp.arange(s)[None]
        caches = tuple(write_at_rows(c, f, rows, cols)
                       for c, f in zip(caches, fresh))
    return caches, tuple(read_dense(c, dtype) for c in caches), cols


@functools.lru_cache(maxsize=None)
def span_ladder(table_width):
    """The widths, in pages, a paged read may be bounded to: eighths of
    the table's width rounded up, duplicates dropped (a 16-page table
    has 8 rungs of 2 pages, a 4-page one 4 of 1), ascending. A
    function of the table's shape alone: the decode program, the
    engine's ``span_tokens`` counter (a call a decode step) and the
    tests all take it from here."""
    return tuple(sorted({-(-table_width * k // 8) for k in range(1, 9)}))


def span_rung(table_width, pos, page_size):
    """Index into :func:`span_ladder` of the narrowest rung that holds
    every row's position: the batch's longest row needs ``max(pos) //
    page_size + 1`` pages. ``pos`` ``[B]`` is a numpy array (the
    engine's counter) or a traced ``jax.numpy`` one (the decode
    program): the same expression serves both. A position past the
    table reads the last rung."""
    need = pos.max() // page_size + 1
    return (need > np.asarray(span_ladder(table_width)[:-1])).sum()


def write_and_attend_paged(caches, fresh, pos, page_table, attend,
                           dtype=None):
    """The cache addressing of a page arena, with the attention it
    feeds: ``caches`` are one layer's arenas ``[pages, page_size,
    ...]`` shared by all rows, ``page_table`` ``[B, P]``, ``pos``
    ``[B]``. Row ``r``'s ONE token lands in page ``table[r, pos[r] //
    page_size]`` at offset ``pos[r] % page_size``. Page 0 is the
    garbage page: free rows (a zeroed table row, ``pos`` 0) write
    there, and nothing reads it but through columns
    :func:`position_mask` closes. The bytes written are BITWISE what
    :func:`write_at_pos` writes for that position: the serving prefix
    cache publishes decode-written pages as reusable prefix KV
    (``tests/test_prefix_cache.py``).

    The read is bounded by the batch's longest row. Of the
    :func:`span_ladder` of the table's width the program picks, on the
    device from ``pos``, the narrowest rung that holds every row
    (:func:`span_rung`), and one ``jax.lax.switch`` runs that rung's
    branch: gather the pages of ``page_table[:, :rung]`` into ``[B,
    rung * page_size, ...]`` views (in the compute ``dtype`` where the
    storage is int8, as stored otherwise), build their position mask
    and call the decoder's own contraction ``attend(views, mask)``.
    Every branch is the whole-table read at a narrower width: the
    columns left out are columns the mask closes for every row, which
    add exact zeros, so the result does not depend on the rung. One
    program serves every span; nothing is chosen on the host. Returns
    ``(new_caches, attend's result)``."""
    s = fresh[0].shape[1]
    if s != 1:
        raise ValueError(
            f"paged decode feeds one token per row (S == 1), got S={s}"
        )
    ps = caches[0].shape[1]
    page = jnp.take_along_axis(page_table, (pos // ps)[:, None],
                               axis=1)[:, 0]
    offset = pos % ps
    caches = tuple(write_paged(c, f[:, 0], page, offset)
                   for c, f in zip(caches, fresh))

    def read(pages, caches, page_table, cols):
        views = tuple(gather_pages_dense(c, page_table[:, :pages], dtype)
                      for c in caches)
        return attend(views, position_mask(cols, pages * ps))

    width = page_table.shape[1]
    out = jax.lax.switch(
        span_rung(width, pos, ps),
        [functools.partial(read, pages) for pages in span_ladder(width)],
        caches, page_table, pos[:, None])
    return caches, out


def position_mask(cols, width):
    """Additive mask ``[B or 1, 1, S, width]`` over a cache view: the
    token at column ``cols[r, t]`` may read slot ``k`` iff ``k <=
    cols[r, t]`` — everything later (stale slots, pad tokens, the
    garbage page, other requests' leftovers) adds an exact zero."""
    valid = jnp.arange(width)[None, None, :] <= cols[:, :, None]
    return jnp.where(valid, 0.0, -jnp.inf)[:, None]


def slab_row_block(cache, slot):
    """Inverse of :func:`adopt_into_slab`: the ``[1, S, ...]`` block of
    decode-slab row ``slot`` (traced) — how the speculative verify
    program materializes one request's KV as a prefill-layout block."""
    if is_quantized(cache):
        return QuantizedKV(
            jax.lax.dynamic_slice_in_dim(cache.q, slot, 1, axis=0),
            jax.lax.dynamic_slice_in_dim(cache.scale, slot, 1, axis=0),
        )
    return jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=0)


def broadcast_rows(cache, n):
    """``[1, S, ...]`` block -> ``[n, S, ...]`` broadcast: the
    speculative verify re-read gives every proposed position its own
    batch row over the SAME written content, so one decode-shaped
    program scores all K+1 positions at per-row positions."""
    if is_quantized(cache):
        return QuantizedKV(
            jnp.broadcast_to(cache.q, (n,) + cache.q.shape[1:]),
            jnp.broadcast_to(cache.scale, (n,) + cache.scale.shape[1:]),
        )
    return jnp.broadcast_to(cache, (n,) + cache.shape[1:])


# ----------------------------------------------------------- adopt programs


def adopt_into_slab(dst, blk, slot):
    """One leaf of the slab engine's adopt program: copy a prefilled
    ``[1, bucket, ...]`` block into decode row ``slot`` (traced)."""
    z = jnp.zeros((), slot.dtype)
    if is_quantized(dst):
        return QuantizedKV(
            jax.lax.dynamic_update_slice(dst.q, blk.q, (slot, z, z, z)),
            jax.lax.dynamic_update_slice(dst.scale, blk.scale,
                                         (slot, z, z)),
        )
    return jax.lax.dynamic_update_slice(
        dst, blk.astype(dst.dtype), (slot,) + (z,) * (dst.ndim - 1)
    )


def gather_block_from_pages(arena, page_ids, n_pages, page_size):
    """The inverse of :func:`adopt_into_pages`: materialize ``n_pages``
    arena pages at traced ``page_ids`` as one prefill-layout block
    ``[1, n_pages * page_size, ...]`` — the serving prefix cache uses it
    to rebuild a request's cached-prefix KV so the chunked prefill can
    attend over it (ids past the cached span point at the garbage page
    0; its content sits behind the position mask like any stale slot)."""
    if is_quantized(arena):
        kvh = arena.q.shape[2]
        d = arena.q.shape[3]
        return QuantizedKV(
            arena.q[page_ids].reshape(1, n_pages * page_size, kvh, d),
            arena.scale[page_ids].reshape(1, n_pages * page_size, kvh),
        )
    return arena[page_ids].reshape(
        (1, n_pages * page_size) + arena.shape[2:]
    )


def adopt_into_pages(arena, blk, page_ids, n_pages, page_size):
    """One leaf of the paged engine's adopt program: scatter a
    prefilled ``[1, bucket, ...]`` block into the arena as ``n_pages``
    whole pages at traced ``page_ids`` (tail ids -> garbage page 0)."""
    if is_quantized(arena):
        kvh = blk.q.shape[2]
        d = blk.q.shape[3]
        return QuantizedKV(
            arena.q.at[page_ids].set(
                blk.q[0].reshape(n_pages, page_size, kvh, d)
            ),
            arena.scale.at[page_ids].set(
                blk.scale[0].reshape(n_pages, page_size, kvh)
            ),
        )
    return arena.at[page_ids].set(
        blk[0].reshape((n_pages, page_size) + blk.shape[2:])
        .astype(arena.dtype)
    )
