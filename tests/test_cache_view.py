"""The cache addressing both decoders share (``quantization/kv.py``:
``write_and_view``, ``write_and_attend_paged`` and ``position_mask``)
against a numpy model of the same cache: three modes (a slab at a
scalar position, a slab at per-row positions, a page arena through a
table) over three payloads (a bf16 K/V pair, an int8 K/V pair, one
latent array).

The model keeps every row's LOGICAL cache ``[B, S_max, ...]`` as numpy
arrays of what is stored (bf16 values, or int8 values and their
scales); a page arena is the same rows cut into pages and laid out at
the table's page ids. Writing and reading there are plain indexing.
"""
import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.quantization import kv as qkv

B, S_MAX, PS = 3, 16, 4
P = S_MAX // PS
PAYLOADS = {            # trailing shapes of the layer's arrays, int8?
    "kv_bf16": (((2, 8), (2, 8)), False),
    "kv_int8": (((2, 8), (2, 8)), True),
    "latent": (((24,),), False),
}
BF16 = ml_dtypes.bfloat16


# ------------------------------------------------------- the numpy model
def np_quantize(x):
    """``quantize_kv`` in numpy: per-vector absmax, the scale rounded
    through bf16."""
    x = np.asarray(x, np.float32)
    scale = (np.maximum(np.abs(x).max(-1), 1e-8) / np.float32(127.0)) \
        .astype(BF16).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale


def np_store(x, int8):
    """What a cache keeps of ``x``: (values, scales or None)."""
    if int8:
        return np_quantize(x)
    return np.asarray(x, np.float32).astype(BF16), None


def np_view(stored, int8):
    """What attention reads of a stored array, as float32."""
    vals, scale = stored
    if int8:
        return vals.astype(np.float32) * scale[..., None]
    return vals.astype(np.float32)


def make(payload, seed):
    """A layer's logical caches with random content (numpy model) and
    random fresh tokens for up to 5 positions a row."""
    trailing, int8 = PAYLOADS[payload]
    rng = np.random.default_rng(seed)
    logical = [np_store(rng.standard_normal((B, S_MAX) + t), int8)
               for t in trailing]
    fresh = [rng.standard_normal((B, 5) + t).astype(np.float32)
             for t in trailing]
    return logical, fresh, int8


def to_device(stored, int8, arena_ids=None):
    """The jax cache array of one stored array: the slab itself, or a
    page arena holding the rows' pages at ``arena_ids`` ``[B, P]``
    (page 0 and every page no table names hold 7s)."""
    def lay(a):
        if arena_ids is None:
            return jnp.asarray(a)
        arena = np.full((B * P + 3, PS) + a.shape[2:], 7, a.dtype)
        arena[arena_ids] = a.reshape((B, P, PS) + a.shape[2:])
        return jnp.asarray(arena)

    vals, scale = stored
    return qkv.QuantizedKV(lay(vals), lay(scale)) if int8 else lay(vals)


def np_write(logical, fresh, pos, s, int8):
    """The model's write: row ``r``'s ``s`` tokens at ``pos[r] + t``."""
    out = []
    for (vals, scale), f in zip(logical, fresh):
        vals = vals.copy()
        scale = None if scale is None else scale.copy()
        for r in range(B):
            v, sc = np_store(f[r, :s], int8)
            vals[r, pos[r]:pos[r] + s] = v
            if int8:
                scale[r, pos[r]:pos[r] + s] = sc
        out.append((vals, scale))
    return out


def as_np(cache):
    """(values, scales or None) of a jax cache array."""
    if qkv.is_quantized(cache):
        return np.asarray(cache.q), np.asarray(cache.scale)
    return np.asarray(cache), None


def assert_stored_equal(got, want):
    for (gv, gs), (wv, ws) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(gv, np.float32),
                                      np.asarray(wv, np.float32))
        if ws is not None:
            np.testing.assert_array_equal(gs, ws)


TABLE = 1 + np.random.default_rng(7).permutation(B * P).reshape(B, P)


def spy(views, mask):
    """An ``attend`` that hands back what the paged read gave it: the
    views and the mask of the rung the program chose, padded to the
    table's width (zeros; closed columns) as one ``switch`` needs."""
    def pad(a, axis, value):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, S_MAX - a.shape[axis])
        return jnp.pad(a.astype(jnp.float32), widths,
                       constant_values=value)
    return tuple(pad(v, 1, 0.0) for v in views), pad(mask, 3, -jnp.inf)


def run(logical, fresh, pos, s, int8, table=None):
    """``write_and_view`` (a slab) or ``write_and_attend_paged`` (an
    arena through ``table``) under jit, as the decoders trace them:
    the new caches, the views as float32 and ``cols``; of a paged read
    the views of the chosen rung, zero-padded, and ``cols`` as its
    mask has them (a row's last open column)."""
    caches = tuple(to_device(st, int8, table) for st in logical)
    tok = tuple(jnp.asarray(f[:, :s]) for f in fresh)
    pos = jnp.asarray(pos, jnp.int32)
    if table is None:
        new, views, cols = jax.jit(lambda c, f, p: qkv.write_and_view(
            c, f, p, jnp.float32))(caches, tok, pos)
    else:
        tbl = jnp.asarray(table, jnp.int32)
        new, (views, mask) = jax.jit(
            lambda c, f, p: qkv.write_and_attend_paged(
                c, f, p, tbl, spy, jnp.float32))(caches, tok, pos)
        assert mask.shape == (B, 1, 1, S_MAX)
        is_open = np.asarray(mask)[:, 0, 0] == 0
        cols = is_open.sum(-1, keepdims=True) - 1
        # open columns are a prefix: slots 0..cols
        assert (is_open == (np.arange(S_MAX)[None] <= cols)).all()
    return new, [np.asarray(v, np.float32) for v in views], np.asarray(cols)


# ----------------------------------------------------------------- cases
CASES = [(mode, payload, s)
         for mode in ("scalar", "rows", "paged")
         for payload in PAYLOADS
         for s in ((1,) if mode == "paged" else (1, 5))]


@pytest.mark.parametrize("mode,payload,s", CASES)
def test_write_and_view_against_the_numpy_cache(mode, payload, s):
    logical, fresh, int8 = make(payload, seed=len(payload) + s)
    pos = {"scalar": np.int32(6), "rows": np.array([0, 9, 11]),
           "paged": np.array([2, 7, 15])}[mode]
    row_pos = np.broadcast_to(pos, (B,))
    want = np_write(logical, fresh, row_pos, s, int8)
    table = TABLE if mode == "paged" else None
    new, views, cols = run(logical, fresh, pos, s, int8, table)

    # cols: the cache column of every fresh token
    want_cols = (row_pos[:1] if mode == "scalar" else row_pos)[:, None] \
        + np.arange(s)[None]
    np.testing.assert_array_equal(cols, want_cols)
    # the view is the rows' logical cache after the write
    for v, st in zip(views, want):
        assert v.shape == (B, S_MAX) + st[0].shape[2:]
        np.testing.assert_array_equal(v, np_view(st, int8))
    # and the cache holds exactly that: a slab as it is; an arena at
    # the table's pages, every other page untouched
    got = [as_np(c) for c in new]
    if table is None:
        assert_stored_equal(got, want)
        return
    for (gv, gs), (wv, ws), st in zip(got, want, logical):
        before = as_np(to_device(st, int8, table))
        for g, w, b in ((gv, wv, before[0]),) + (
                ((gs, ws, before[1]),) if int8 else ()):
            np.testing.assert_array_equal(
                np.asarray(g[table], np.float32).reshape(w.shape),
                np.asarray(w, np.float32))
            others = np.setdiff1d(np.arange(g.shape[0]), table)
            np.testing.assert_array_equal(
                np.asarray(g[others], np.float32),
                np.asarray(b[others], np.float32))


@pytest.mark.parametrize("payload", ["kv_bf16", "latent"])
def test_paged_rows_cross_a_page_boundary(payload):
    """Three steps of one row, the last slot of a page and the first
    two of the next: each token lands in the page the table names for
    its position, at ``pos % page_size``."""
    logical, fresh, int8 = make(payload, seed=1)
    caches = tuple(to_device(st, int8, TABLE) for st in logical)
    tbl = jnp.asarray(TABLE, jnp.int32)
    step = jax.jit(lambda c, f, p: qkv.write_and_attend_paged(
        c, f, p, tbl, spy))
    for t, p in enumerate((PS - 1, PS, PS + 1)):
        tok = tuple(jnp.asarray(f[:, t:t + 1]) for f in fresh)
        caches, (views, _) = step(caches, tok,
                                  jnp.full((B,), p, jnp.int32))
    # the read stopped at the second of four pages
    assert all(not np.asarray(v[:, 2 * PS:]).any() for v in views)
    for cache, view, f in zip(caches, views, fresh):
        stored = f[:, :3].astype(BF16).astype(np.float32)
        for r in range(B):
            first, second = TABLE[r, 0], TABLE[r, 1]
            got = np.concatenate([np.asarray(cache[first, PS - 1:]),
                                  np.asarray(cache[second, :2])])
            np.testing.assert_array_equal(got.astype(np.float32), stored[r])
        np.testing.assert_array_equal(
            np.asarray(view[:, PS - 1:PS + 2], np.float32), stored)


def test_free_rows_write_page_0_and_the_mask_never_reads_it():
    """A free row's table is all zeros: its token lands on the garbage
    page 0, no page of a live row changes, and every column of page 0
    that a live row's view holds (its table's unclaimed tail) lies
    behind ``position_mask``."""
    logical, fresh, int8 = make("latent", seed=2)
    table = TABLE.copy()
    table[1] = 0                      # row 1 is free
    table[0, 2:] = 0                  # row 0 claimed two pages so far
    pos = np.array([5, 0, 9])
    (cache,), (view,), cols = (
        x for x in run(logical, fresh, pos, 1, int8, table))
    before = np.asarray(to_device(logical[0], int8, table), np.float32)
    after = np.asarray(cache, np.float32)
    changed = np.unique(np.nonzero((before != after).any(-1))[0])
    assert set(changed) == {0, table[0, 1], table[2, 2]}
    np.testing.assert_array_equal(
        after[0, 0], fresh[0][1, 0].astype(BF16).astype(np.float32))
    mask = np.asarray(qkv.position_mask(jnp.asarray(cols), S_MAX))
    assert mask.shape == (B, 1, 1, S_MAX)
    open_cols = mask[:, 0, 0] == 0
    np.testing.assert_array_equal(open_cols.sum(-1), pos + 1)
    # the columns of row 0's view that come from page 0 are all closed
    from_page_0 = np.repeat(table[0] == 0, PS)
    assert from_page_0.sum() == 2 * PS
    assert not (open_cols[0] & from_page_0).any()


def test_more_than_one_token_through_a_table_is_refused():
    logical, fresh, int8 = make("kv_bf16", seed=3)
    with pytest.raises(ValueError, match=r"S == 1\), got S=5"):
        run(logical, fresh, np.array([0, 1, 2]), 5, int8, TABLE)


def test_int8_paged_write_is_bitwise_the_block_write():
    """The prefix cache's pin: the bytes a paged decode step writes for
    a position are the bytes ``write_at_pos`` (prefill) writes there,
    values and scales."""
    logical, fresh, int8 = make("kv_int8", seed=4)
    pos = np.array([3, 6, 13])
    new, _, _ = run(logical, fresh, pos, 1, int8, TABLE)
    for cache, st, f in zip(new, logical, fresh):
        for r in range(B):
            block = qkv.write_at_pos(
                to_device((st[0][r:r + 1], st[1][r:r + 1]), int8),
                jnp.asarray(f[r:r + 1, :1]), jnp.int32(pos[r]))
            page, off = TABLE[r, pos[r] // PS], pos[r] % PS
            np.testing.assert_array_equal(
                np.asarray(cache.q[page, off]),
                np.asarray(block.q[0, pos[r]]))
            np.testing.assert_array_equal(
                np.asarray(cache.scale[page, off]),
                np.asarray(block.scale[0, pos[r]]))


def test_position_mask_opens_slots_up_to_each_tokens_column():
    cols = jnp.asarray([[0, 1, 2], [4, 5, 6]])
    mask = np.asarray(qkv.position_mask(cols, 8))
    assert mask.shape == (2, 1, 3, 8)
    for r in range(2):
        for t in range(3):
            c = int(cols[r, t])
            assert (mask[r, 0, t, :c + 1] == 0).all()
            assert np.isneginf(mask[r, 0, t, c + 1:]).all()
    # a scalar position's one row of columns serves every batch row
    assert qkv.position_mask(jnp.asarray([[3]]), 8).shape == (1, 1, 1, 8)
