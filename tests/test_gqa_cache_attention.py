"""Grouped-query attention over a KV cache without the repeated copy.

``_sdpa_grouped_ref`` contracts the query heads, grouped by their KV
head, against K and V as the cache stores them. The reference here is
the formulation it replaced: ``repeat`` K and V to ``H`` heads, then
``_sdpa_ref``. The two are not bitwise equal (another contraction
shape), so the tolerances are set from the dtype: 1e-6 in fp32, one
ulp in bf16.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig
from paddle_tpu.models.llama import LlamaAttention
from paddle_tpu.nn.functional.attention import _sdpa_grouped_ref, _sdpa_ref
from paddle_tpu.quantization.kv import (
    QuantizedKV,
    gather_pages_dense,
    quantize_kv,
)

B, S, H, KVH, D = 3, 2, 8, 2, 16
PS, PAGES = 4, 5                       # a row's table spans 20 columns
REP = H // KVH
SCALE = D ** -0.5


def _close(got, want, dtype):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    else:  # one bf16 ulp of the larger magnitude (8 bits of mantissa)
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(
            np.maximum(np.abs(got), np.abs(want)), 1e-30))) - 7)
        assert (np.abs(got - want) <= ulp).all(), np.abs(got - want).max()


def _arena(rng, dtype, int8):
    """A page arena pair, each row's table and per-row positions."""
    n = B * PAGES + 1
    k = jnp.asarray(rng.randn(n, PS, KVH, D), dtype)
    v = jnp.asarray(rng.randn(n, PS, KVH, D), dtype)
    if int8:
        k, v = QuantizedKV(*quantize_kv(k)), QuantizedKV(*quantize_kv(v))
    tbl = jnp.asarray(
        1 + rng.permutation(B * PAGES).reshape(B, PAGES), jnp.int32)
    pos = jnp.asarray([3, 17, 9], jnp.int32)    # per-row decode depths
    return k, v, tbl, pos


def _mask(rng, pos, extra):
    """The positional mask of the cache branches for ``S`` new tokens a
    row at per-row positions, plus an extra additive mask."""
    cols = pos[:, None] + jnp.arange(S)[None, :]
    valid = jnp.arange(PAGES * PS)[None, None, :] <= cols[:, :, None]
    mask = jnp.where(valid, 0.0, -jnp.inf)[:, None, :, :]
    if extra == "rows":         # e.g. left padding: column 0 masked out
        am = np.zeros((B, 1, 1, PAGES * PS), np.float32)
        am[:, :, :, 0] = -np.inf
        mask = mask + jnp.asarray(am)
    elif extra == "per_head":   # a bias that differs by query head
        mask = mask + jnp.asarray(
            rng.randn(B, H, S, PAGES * PS), jnp.float32)
    return mask


@pytest.mark.parametrize("extra", [None, "rows", "per_head"])
@pytest.mark.parametrize("int8", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_grouped_matches_repeat_then_sdpa(dtype, int8, extra):
    rng = np.random.RandomState(11)
    k_pages, v_pages, tbl, pos = _arena(rng, dtype, int8)
    q = jnp.asarray(rng.randn(B, S, H, D), dtype)
    kk = gather_pages_dense(k_pages, tbl, dtype)
    vv = gather_pages_dense(v_pages, tbl, dtype)
    mask = _mask(rng, pos, extra)
    got = _sdpa_grouped_ref(q, kk, vv, mask, scale=SCALE)
    want = _sdpa_ref(
        q, jnp.repeat(kk, REP, axis=2), jnp.repeat(vv, REP, axis=2), mask,
        causal=False, scale=SCALE, dropout_p=0.0, key=None)
    assert got.shape == (B, S, H, D) and got.dtype == dtype
    _close(got, want, dtype)


def _twin_layers():
    """A GQA attention layer and its MHA twin: the twin's k/v
    projections hold each KV head's columns ``rep`` times, so its
    (untouched) MHA path computes exactly "repeat, then SDPA"."""
    paddle.seed(3)
    cfg = LlamaConfig.tiny(hidden_size=H * D, num_attention_heads=H,
                           num_key_value_heads=KVH)
    gqa = LlamaAttention(cfg)
    mha = LlamaAttention(LlamaConfig.tiny(hidden_size=H * D,
                                          num_attention_heads=H))
    for name in ("q_proj", "o_proj"):
        getattr(mha, name).weight.value = getattr(gqa, name).weight.value
    for name in ("k_proj", "v_proj"):
        w = getattr(gqa, name).weight.value            # [hidden, kvH * D]
        w = jnp.repeat(w.reshape(-1, KVH, D), REP, axis=1)
        getattr(mha, name).weight.value = w.reshape(-1, H * D)
    gqa.eval()
    mha.eval()
    return gqa, mha


@pytest.mark.parametrize("branch", ["slab_scalar", "slab_rows", "paged",
                                    "paged_int8"])
def test_cache_branches_keep_the_head_order(branch):
    """Through ``LlamaAttention.forward``: query head ``h`` attends KV
    head ``h // rep`` in every cache branch, as the cache-less forward's
    ``repeat_interleave`` has it (a wrong order would still leave paged
    == slab == generate)."""
    gqa, mha = _twin_layers()
    rng = np.random.RandomState(13)
    s_max = PAGES * PS
    x = Tensor(jnp.asarray(rng.randn(B, 1 if "paged" in branch else S,
                                     H * D), jnp.float32))

    def caches(heads):
        if branch.startswith("slab"):
            c = jnp.asarray(rng.randn(B, s_max, KVH, D), jnp.float32)
            c = jnp.repeat(c, heads // KVH, axis=2)
            return c, c * 0.5
        c = jnp.asarray(rng.randn(B * PAGES + 1, PS, KVH, D), jnp.float32)
        c = jnp.repeat(c, heads // KVH, axis=2)
        if branch == "paged_int8":   # scales are per (slot, head)
            return (QuantizedKV(*quantize_kv(c)),
                    QuantizedKV(*quantize_kv(c * 0.5)))
        return c, c * 0.5

    state = rng.get_state()
    outs = []
    for layer, heads in ((gqa, KVH), (mha, H)):
        rng.set_state(state)        # the same cache contents for both
        kw = dict(cache=caches(heads))
        if branch == "slab_scalar":
            kw["pos"] = jnp.asarray(5, jnp.int32)
        else:
            kw["pos"] = jnp.asarray([3, 17, 9], jnp.int32)
        if "paged" in branch:
            kw["page_table"] = jnp.asarray(
                1 + np.arange(B * PAGES).reshape(B, PAGES), jnp.int32)
        out, _ = layer(x, **kw)
        outs.append(np.asarray(out.value))
    np.testing.assert_allclose(outs[0], outs[1], rtol=0, atol=2e-6)
