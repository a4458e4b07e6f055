"""Solar-Open2 decoder (KDA gated-delta-rule layers whose state is kept
a row, gated NoPE GQA layers whose K/V is kept a token, sigmoid-routed
experts of which a share is held) at a toy size on the CPU, float32:
the program against the plain reference in ``benchmarks/reference/``,
through the model's own forward, through both kinds of cache and
through the serving engines."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (
    LlamaConfig,
    SolarOpen2Config,
    SolarOpen2ForCausalLM,
    Xing4Config,
)
from paddle_tpu.models import generation, solar_open2, xing4
from paddle_tpu.quantization import kv as qkv
from paddle_tpu.serving import PagedServingEngine, ServingEngine
from paddle_tpu.serving.paged_pool import PagedKVPool

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.reference import linear_moe_decoder as ref  # noqa: E402

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "linear_attn_config",
        "gqa_interval", "gqa_layers", "kda_allow_neg_eigval",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "rms_norm_eps")


def ref_config(cfg):
    """The program's config as the reference's dict (a configuration
    file's keys: ``n_routed_experts`` counts the experts held)."""
    out = {k: getattr(cfg, k) for k in KEYS}
    out.update(n_routed_experts=cfg.held, experts_first=cfg.experts_first,
               published={"n_routed_experts": cfg.n_routed_experts})
    return out


def build(seed=0, **kw):
    """A toy net (hidden 64; GQA 4 query / 2 KV heads of 16; KDA 4 heads
    of 16, chunks of 8; 16 experts top-4 + shared; one period of 4
    layers) with the program's own seeded initializers, its config as
    the reference's dict and its weights by name."""
    paddle.seed(seed)
    cfg = SolarOpen2Config.tiny(**kw)
    net = SolarOpen2ForCausalLM(cfg)
    net.eval()
    return net, ref_config(cfg), \
        {k: p.value for k, p in net.named_parameters()}


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def toy_share():
    """Experts 4..7 of 16 held."""
    return build(experts_first=4, experts_held=4)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, (n,))


def _forward(net, ids):
    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor

    def run(ids):
        with tape.trace_scope(), tape.no_grad():
            return net(Tensor(ids)).value

    return jax.jit(run)(jnp.asarray(ids))


def _kda_inputs(s, seed=0, b=2, h=3, d=8):
    """Random inputs of the state update as the mixer makes them."""
    r = np.random.default_rng(seed)
    l2 = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = l2(r.normal(size=(b, s, h, d))) * d ** -0.5
    k = l2(r.normal(size=(b, s, h, d)))
    v = r.normal(size=(b, s, h, d))
    g = -r.uniform(0.01, 1.6, size=(b, s, h, d))
    beta = r.uniform(0.0, 2.0, size=(b, s, h))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


# ------------------------------------------------------------ whole model
@pytest.mark.parametrize("which", ["all_experts", "a_share"])
def test_logits_match_the_reference(which, toy, toy_share):
    net, cfg, w = toy if which == "all_experts" else toy_share
    ids = _ids(21, 1)
    got = _forward(net, ids[None])[0]
    want = ref.logits(w, cfg, jnp.asarray(ids))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_layer_kinds_follow_the_published_period():
    cfg = SolarOpen2Config()
    kinds = [cfg.is_gqa(i) for i in range(cfg.num_hidden_layers)]
    assert [i for i, g in enumerate(kinds) if g] == list(range(0, 48, 4))
    listed = SolarOpen2Config(gqa_layers=tuple(range(0, 48, 4)))
    assert [listed.is_gqa(i) for i in range(48)] == kinds
    net = build()[0]
    assert [type(layer.mixer).__name__ for layer in net.model.layers] == \
        ["SolarOpen2Attention"] + ["SolarOpen2KDA"] * 3


# ----------------------------------------------------------- the KDA forms
@pytest.mark.parametrize("s, chunk, atol", [
    (1, 8, 2e-6), (5, 8, 2e-6), (8, 8, 2e-6), (16, 8, 2e-6), (19, 8, 2e-6),
    # four sub-blocks of 16 a chunk: whole chunks and a ragged one, so
    # every diagonal block and every pair of sub-blocks runs. A system
    # of 64 tokens over 8-wide keys rounds to 1-5e-6 of a state of 2-3
    # in float32 (the scan as it stood before the sub-blocks read the
    # same against a float64 recurrence, to a few per cent)
    (64, 64, 1e-5), (100, 64, 1e-5), (128, 64, 1e-5)])
def test_chunked_scan_equals_the_token_scan(s, chunk, atol):
    """Chunks of 8 (one sub-block) and of 64 (four) over lengths that
    are and are not whole chunks, against the reference's recurrence a
    token at a time; decays down to exp(-1.6) a token."""
    q, k, v, g, beta = _kda_inputs(s, seed=s)
    zero = jnp.zeros((2, 3, 8, 8), jnp.float32)
    o, state = jax.jit(lambda *a: solar_open2.kda_scan(*a, zero, chunk))(
        q, k, v, g, beta)
    for b in range(2):
        want_o, want_s = ref.kda_recurrence(q[b], k[b], v[b], jnp.exp(g[b]),
                                            beta[b])
        np.testing.assert_allclose(o[b], want_o, atol=atol)
        np.testing.assert_allclose(state[b], want_s, atol=atol)


@pytest.mark.parametrize("s", [64, 160])
def test_strong_decay_over_a_long_chunk_stays_finite(s):
    """64 tokens of decay exp(-1.6) each are exp(-102) end to end, and
    160 cross sub-block and chunk boundaries with a state carried into
    the second and third chunk: the chunked form never forms the
    reciprocal of a decay."""
    q, k, v, g, beta = _kda_inputs(s, seed=3, b=1)
    g = jnp.full_like(g, -1.6)
    zero = jnp.zeros((1, 3, 8, 8), jnp.float32)
    o, state = solar_open2.kda_scan(q, k, v, g, beta, zero, 64)
    want_o, want_s = ref.kda_recurrence(q[0], k[0], v[0], jnp.exp(g[0]),
                                        beta[0])
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(want_s)).max() > 0.1
    np.testing.assert_allclose(o[0], want_o, atol=2e-6)
    np.testing.assert_allclose(state[0], want_s, atol=2e-6)


@pytest.mark.parametrize("chunk, first, s", [(8, 11, 30), (64, 37, 150)])
def test_scan_from_a_state_continues_the_token_scan(chunk, first, s):
    """A scan that starts from the state ``first`` tokens left (not
    zero) gives the rest of the sequence's outputs and its last
    state."""
    q, k, v, g, beta = _kda_inputs(s, seed=first, b=1)
    want_o, want_s = ref.kda_recurrence(q[0], k[0], v[0], jnp.exp(g[0]),
                                        beta[0])
    head = lambda a: a[:, :first]
    _, state = ref.kda_recurrence(*(head(a)[0] for a in (q, k, v)),
                                  jnp.exp(head(g)[0]), head(beta)[0])
    assert np.abs(np.asarray(state)).max() > 0.1
    rest = lambda a: a[:, first:]
    o, last = solar_open2.kda_scan(rest(q), rest(k), rest(v), rest(g),
                                   rest(beta), state[None], chunk)
    np.testing.assert_allclose(o[0], want_o[first:], atol=2e-6)
    np.testing.assert_allclose(last[0], want_s, atol=2e-6)


@pytest.mark.parametrize("group", [6, 12, 18])
def test_scan_by_groups_of_chunks_is_the_scan_at_once(group, monkeypatch):
    """The chunk-local part is made for ``_KDA_GROUP`` chunk-heads at a
    time: 1, 2 and 3 chunks of the 5 here (2 rows x 3 heads), the last
    group filled with frozen chunks; every grouping gives the bits of
    the whole sequence at once."""
    q, k, v, g, beta = _kda_inputs(37, seed=5)
    start = jnp.asarray(np.random.default_rng(6).normal(size=(2, 3, 8, 8)),
                        jnp.float32)
    want = solar_open2.kda_scan(q, k, v, g, beta, start, 8)
    monkeypatch.setattr(solar_open2, "_KDA_GROUP", group)
    got = solar_open2.kda_scan(q, k, v, g, beta, start, 8)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_one_token_step_equals_the_token_scan():
    q, k, v, g, beta = _kda_inputs(6, seed=9)
    state = jnp.zeros((2, 3, 8, 8), jnp.float32)
    outs = []
    for t in range(6):
        o, state = solar_open2.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                        beta[:, t], state)
        outs.append(o)
    for b in range(2):
        want_o, want_s = ref.kda_recurrence(q[b], k[b], v[b], jnp.exp(g[b]),
                                            beta[b])
        np.testing.assert_allclose(jnp.stack(outs, 1)[b], want_o, atol=2e-6)
        np.testing.assert_allclose(state[b], want_s, atol=2e-6)


@pytest.mark.parametrize("length", [3, 8, 13])
def test_scan_frozen_past_length_is_bitwise_the_short_scan(length):
    """Tokens from ``length`` on, whatever they hold, leave the state
    bitwise what the first ``length`` tokens made it."""
    q, k, v, g, beta = _kda_inputs(24, seed=length, b=1)
    zero = jnp.zeros((1, 3, 8, 8), jnp.float32)
    cut = lambda a: a[:, :length]
    _, short = solar_open2.kda_scan(cut(q), cut(k), cut(v), cut(g),
                                    cut(beta), zero, 8)
    o, frozen = solar_open2.kda_scan(q, k, v, g, beta, zero, 8,
                                     length=jnp.int32(length))
    assert np.array_equal(np.asarray(short), np.asarray(frozen))


def test_conv_tail_is_taken_at_length():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 6)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(4, 6)),
                    jnp.float32)
    y, tail = solar_open2.kda_conv(x, w, None, jnp.int32(5))
    np.testing.assert_array_equal(tail, x[:, 2:5])
    # continuing from the tail equals convolving the whole sequence
    y2, tail2 = solar_open2.kda_conv(x[:, 5:], w, tail)
    whole, last = solar_open2.kda_conv(x, w)
    np.testing.assert_allclose(y2, whole[:, 5:], atol=1e-6)
    np.testing.assert_array_equal(tail2, last)
    # fewer tokens than taps: zeros before the sequence's start
    _, early = solar_open2.kda_conv(x, w, None, jnp.int32(2))
    np.testing.assert_array_equal(early[:, 0], jnp.zeros((1, 6)))
    np.testing.assert_array_equal(early[:, 1:], x[:, :2])


# ------------------------------------------------------------ the caches
def test_cache_statement_names_token_and_row_arrays(toy):
    net = toy[0]
    cfg, ps = net.config, 8
    pair = ((2, 16), (2, 16))
    assert generation.cache_layout(cfg) == [pair, (), (), ()]
    kept = (((4, 16, 16), "float32"), ((3, 192), None))
    assert generation.row_layout(cfg) == [(), kept, kept, kept]
    assert generation.keeps_row_state(cfg)
    assert not generation.keeps_kv_pairs(cfg)
    assert generation.row_array_mask(cfg) == [False] * 2 + [True] * 6
    # the published sizes: 4096 B a token, 13.0 MB a row a period
    full = SolarOpen2Config(num_hidden_layers=4)
    assert generation.cache_token_bytes(full, "bfloat16") == 4096
    assert generation.cache_row_bytes(full, "bfloat16") == \
        3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) == 13025280

    pool = PagedKVPool(cfg, page_size=ps, num_pages=5, dtype="bfloat16",
                       max_seq_len=32)
    # page accounting counts the GQA layer's K and V alone
    assert pool.page_bytes() == ps * 2 * 2 * 16 * 2
    assert pool.row_bytes() == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    arena = pool.alloc_arena_arrays(rows=3)
    assert [a.shape for a in arena[0]] == [(6, ps, 2, 16)] * 2
    for layer in arena[1:]:
        assert [(a.shape, a.dtype) for a in layer] == [
            ((3, 4, 16, 16), jnp.float32), ((3, 3, 192), jnp.bfloat16)]
    slab = generation.alloc_kv_caches(cfg, 2, 16, "float32")
    assert [a.shape for a in slab[0]] == [(2, 16, 2, 16)] * 2
    assert [a.shape for a in slab[1]] == [(2, 4, 16, 16), (2, 3, 192)]
    flat = [a for layer in slab for a in layer]
    again = generation.unflatten_caches(flat, cfg)
    assert [len(layer) for layer in again] == [2, 2, 2, 2]
    assert all(a is b for la, lb in zip(again, slab) for a, b in zip(la, lb))


@pytest.mark.parametrize("cfg", [
    LlamaConfig.tiny(num_key_value_heads=2), Xing4Config.tiny()],
    ids=["llama", "xing4"])
def test_a_net_that_states_no_row_arrays_gets_none(cfg):
    n = cfg.num_hidden_layers
    assert generation.row_layout(cfg) == [()] * n
    assert not generation.keeps_row_state(cfg)
    assert generation.cache_row_bytes(cfg, "bfloat16") == 0
    per_layer = len(generation.cache_layout(cfg)[0])
    assert generation.row_array_mask(cfg) == [False] * (n * per_layer)
    pool = PagedKVPool(cfg, page_size=8, num_pages=5, dtype="bfloat16",
                       max_seq_len=32)
    assert pool.row_bytes() == 0
    with_rows = pool.alloc_arena_arrays(rows=7)
    plain = pool.alloc_arena_arrays()
    assert [[a.shape for a in layer] for layer in with_rows] == \
        [[a.shape for a in layer] for layer in plain]
    assert all(len(layer) == per_layer for layer in plain)


@pytest.mark.parametrize("cache", ["slab", "paged"])
def test_prefill_then_decode_gives_the_reference_at_every_position(
        toy_share, cache):
    """A right-padded bucketed prefill, then one-token steps through a
    slab (rows at their own positions) or through pages with the row
    state beside them, teacher-forced: every logits row against the
    reference's full forward."""
    net, cfg, w = toy_share
    n, bucket, total, ps = 11, 16, 19, 8
    ids = _ids(total, 5)
    want = np.asarray(ref.logits(w, cfg, jnp.asarray(ids)))
    padded = np.zeros((1, bucket), np.int64)
    padded[0, :n] = ids[:n]
    block = generation.alloc_kv_caches(net.config, 1, bucket, "float32")
    row0, block = jax.jit(lambda i, c: generation.prefill(
        net, i, c, length=n))(jnp.asarray(padded), block)
    np.testing.assert_allclose(row0[0], want[n - 1], atol=2e-5)
    by_row = generation.row_array_mask(net.config)
    flat_block = [a for layer in block for a in layer]
    if cache == "paged":
        pool = PagedKVPool(net.config, page_size=ps, num_pages=6,
                           dtype="float32", max_seq_len=32)
        pages = jnp.asarray([4, 2])
        flat = [a for layer in pool.alloc_arena_arrays(rows=2)
                for a in layer]
        flat = [qkv.adopt_into_slab(a, b, jnp.int32(1)) if is_row
                else qkv.adopt_into_pages(a, b, pages, bucket // ps, ps)
                for a, b, is_row in zip(flat, flat_block, by_row)]
        kw = {"page_table": jnp.asarray([[0, 0, 0, 0], [4, 2, 5, 0]])}
    else:
        flat = [a for layer in generation.alloc_kv_caches(
            net.config, 2, 32, "float32") for a in layer]
        flat = [qkv.adopt_into_slab(a, b, jnp.int32(1))
                for a, b in zip(flat, flat_block)]
        kw = {}
    caches = generation.unflatten_caches(flat, net.config)
    step = jax.jit(lambda t, c, p: generation.decode_step(
        net, t, c, p, **kw))
    for pos in range(n, total):
        tok = jnp.asarray([[0], [ids[pos]]])
        logits, caches = step(tok, caches, jnp.asarray([0, pos]))
        np.testing.assert_allclose(logits[1], want[pos], atol=2e-5)


def test_padded_bucket_leaves_the_state_of_the_unpadded_prompt(toy):
    """The state and tail a right-padded bucket leaves are those the
    unpadded prompt leaves, and bitwise the same whatever the pad
    tokens are: pad tokens would keep updating a recurrence, so the
    scan freezes it at ``length`` (the scan alone, at one shape, is
    held bitwise to the short scan above; two programs of different
    lengths round their projections apart in the last bit)."""
    net = toy[0]
    n, bucket = 11, 16
    ids = _ids(n, 6)

    def run(tokens, length):
        block = generation.alloc_kv_caches(net.config, 1, len(tokens),
                                           "float32")
        _, block = jax.jit(lambda i, c: generation.prefill(
            net, i, c, length=length))(jnp.asarray(tokens)[None], block)
        return [np.asarray(a) for layer in block[1:] for a in layer]

    def padded(pad):
        out = np.full((bucket,), pad, np.int64)
        out[:n] = ids
        return out

    bare = run(ids, None)
    zeros, other = run(padded(0), n), run(padded(255), n)
    for a, b, c in zip(zeros, other, bare):
        assert np.array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=0, atol=5e-6)
    # and without ``length`` the pad tokens DO move it
    moved = run(padded(0), None)
    assert np.abs(moved[0] - bare[0]).max() > 1e-3


# --------------------------------------------------------------- experts
@pytest.mark.parametrize("routing", ["random", "none_held_chosen"])
def test_dispatch_of_a_share_adds_the_held_terms_alone(routing):
    r = np.random.default_rng(2)
    t, k, e, c, i = 9, 3, 8, 16, 8
    h = jnp.asarray(r.normal(size=(t, c)), jnp.float32)
    gu = jnp.asarray(r.normal(size=(e, c, 2 * i)) * 0.3, jnp.float32)
    dn = jnp.asarray(r.normal(size=(e, i, c)) * 0.3, jnp.float32)
    idx = np.stack([r.permutation(e)[:k] for _ in range(t)])
    if routing == "none_held_chosen":
        idx = idx % 2                   # experts 0 and 1: not of [2, 5)
    w = jnp.asarray(r.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    first, held = 2, 3
    got = xing4.moe_dispatch(h, jnp.asarray(idx), w, gu[first:first + held],
                             dn[first:first + held], first=first, held=held)
    want = np.zeros((t, c))
    for tok in range(t):
        for j in range(k):
            ex = idx[tok, j]
            if first <= ex < first + held:
                want[tok] += float(w[tok, j]) * np.asarray(
                    ref._swiglu(h[tok:tok + 1], gu[ex], dn[ex]))[0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # every expert held is the layer it was
    np.testing.assert_allclose(
        xing4.moe_dispatch(h, jnp.asarray(idx), w, gu, dn, first=0, held=e),
        xing4.moe_dispatch(h, jnp.asarray(idx), w, gu, dn), atol=1e-6)


def test_a_dispatch_told_no_share_traces_the_program_it_always_did():
    """``held`` None adds no operation: a net with every expert resident
    traces the dispatch as it stood before a layer could be told its
    share (written out here), operation for operation."""
    def as_it_stood(h, idx, w, w_gate_up, w_down):
        t, k = idx.shape
        n_exp, _, two_i = w_gate_up.shape
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=n_exp).astype(jnp.int32)
        xs = h[order // k]
        gu = jax.lax.ragged_dot(xs, w_gate_up, sizes)
        act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
        ys = jax.lax.ragged_dot(act, w_down, sizes)
        back = ys[jnp.argsort(order)].reshape(t, k, -1)
        return jnp.sum(back.astype(jnp.float32) * w[..., None],
                       axis=1).astype(h.dtype)

    args = (jnp.zeros((4, 8)), jnp.zeros((4, 2), jnp.int32),
            jnp.ones((4, 2)), jnp.zeros((3, 8, 8)), jnp.zeros((3, 4, 8)))
    plain = jax.make_jaxpr(lambda *a: xing4.moe_dispatch(*a))(*args)
    assert str(plain) == str(jax.make_jaxpr(as_it_stood)(*args))
    told = jax.make_jaxpr(
        lambda *a: xing4.moe_dispatch(*a, first=0, held=3))(*args)
    assert len(told.jaxpr.eqns) > len(plain.jaxpr.eqns)


def _share_as_it_stood(h, idx, w, w_gate_up, w_down, first, held):
    """``moe_dispatch`` told a share before it bounded its rows: every
    one of the ``T k`` sorted rows through both grouped matmuls."""
    t, k = idx.shape
    n_exp, _, two_i = w_gate_up.shape
    local = idx.reshape(-1) - first
    here = (local >= 0) & (local < held)
    flat = jnp.where(here, local, held)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n_exp).astype(jnp.int32)
    xs = h[order // k]
    gu = jax.lax.ragged_dot(xs, w_gate_up, sizes)
    act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
    ys = jax.lax.ragged_dot(act, w_down, sizes)
    back = ys[jnp.argsort(order)].reshape(t, k, -1)
    back = jnp.where(here.reshape(t, k, 1), back, 0)
    return jnp.sum(back.astype(jnp.float32) * w[..., None],
                   axis=1).astype(h.dtype)


@pytest.mark.parametrize("rows, ladder", [
    (512, (128, 256, 512)), (16384, (4096, 8192, 16384)),
    (8192, (2048, 4096, 8192)), (256, (128, 256)), (160, (128, 160)),
    (130, (128, 130)), (128, (128,)), (27, (27,)), (1, (1,))])
def test_row_ladder_is_a_quarter_a_half_and_all_above_a_floor(rows, ladder):
    assert xing4.row_ladder(rows) == ladder
    for n_local in {0, 1, rows, *ladder, *(r + 1 for r in ladder[:-1])}:
        held = int(xing4.dispatch_rows(rows, n_local))
        assert held == min(r for r in ladder if r >= n_local)


# 5 of 40 experts held, top-8: the benchmark cell's routing at toy widths
_SHARE = dict(first=10, held=5, n_routed=40, k=8, c=16, i=8)
_SHARE_SHAPES = {"decode": 64, "prefill": 2048}


@pytest.fixture(scope="module")
def share_programs():
    """Both dispatches jitted once a shape, with the weights they share."""
    g = _SHARE
    r = np.random.default_rng(5)
    gu = jnp.asarray(r.normal(size=(g["held"], g["c"], 2 * g["i"])) * 0.3,
                     jnp.float32)
    dn = jnp.asarray(r.normal(size=(g["held"], g["i"], g["c"])) * 0.3,
                     jnp.float32)
    share = dict(first=g["first"], held=g["held"])
    stood = jax.jit(lambda h, idx, w: _share_as_it_stood(
        h, idx, w, gu, dn, **share))
    laddered = jax.jit(lambda h, idx, w: xing4.moe_dispatch(
        h, idx, w, gu, dn, **share))
    return stood, laddered


@pytest.mark.parametrize("local", [
    "none", "under_first", "at_first", "over_first", "at_second",
    "over_second", "one_absent", "every"])
@pytest.mark.parametrize("shape", _SHARE_SHAPES)
def test_a_share_bounded_to_a_rung_is_the_dispatch_as_it_stood(
        share_programs, shape, local):
    """Whatever rung the local count picks (none local, under, exactly
    at and one above each rung, every assignment local: the full-length
    rung), the result is the one the dispatch gave over all ``T k``
    rows."""
    g = _SHARE
    t, k = _SHARE_SHAPES[shape], g["k"]
    a, b, full = xing4.row_ladder(t * k)
    n_local, rung = {
        "none": (0, a), "under_first": (a // 2, a), "at_first": (a, a),
        "over_first": (a + 1, b), "at_second": (b, b),
        "over_second": (b + 1, full), "one_absent": (full - 1, full),
        "every": (full, full)}[local]
    assert int(xing4.dispatch_rows(t * k, n_local)) == rung
    r = np.random.default_rng(n_local)
    absent = np.setdiff1d(np.arange(g["n_routed"]),
                          np.arange(g["first"], g["first"] + g["held"]))
    flat = r.choice(absent, size=t * k)
    at = r.permutation(t * k)[:n_local]
    flat[at] = r.integers(g["first"], g["first"] + g["held"], size=n_local)
    idx = jnp.asarray(flat.reshape(t, k), jnp.int32)
    h = jnp.asarray(r.normal(size=(t, g["c"])), jnp.float32)
    w = jnp.asarray(r.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    stood, laddered = share_programs
    want, got = np.asarray(stood(h, idx, w)), np.asarray(laddered(h, idx, w))
    assert np.abs(want).max() > 0 or n_local == 0
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The routed parts the 4 shares of 4 experts compute, plus the
    shared expert ONCE, equal the uncut reference layer; in the program
    and in the reference."""
    net, cfg, w = toy
    mlp = net.model.layers[1].mlp
    prefix = "model.layers.1.mlp."
    lw = {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}
    h = jnp.asarray(np.random.default_rng(4).normal(size=(13, 64)),
                    jnp.float32)
    moe = ref.moe_static(cfg)
    routed, shared, chosen, _ = ref.expert_ffn(h, lw, moe=moe, share=(0, 16))
    uncut = np.asarray(routed + shared)
    idx, wts = mlp.route(paddle.to_tensor(h))
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(chosen), -1))
    program, reference = np.zeros_like(uncut), np.zeros_like(uncut)
    for first in range(0, 16, 4):
        cut = dict(lw, experts_gate_up=lw["experts_gate_up"][first:first + 4],
                   experts_down=lw["experts_down"][first:first + 4])
        part, _, _, _ = ref.expert_ffn(h, cut, moe=moe, share=(first, 4))
        reference += np.asarray(part)
        program += np.asarray(xing4.moe_dispatch(
            h, idx, wts.value, cut["experts_gate_up"], cut["experts_down"],
            first=first, held=4))
    shared = np.asarray(shared)
    np.testing.assert_allclose(reference + shared, uncut, atol=1e-5)
    np.testing.assert_allclose(program + shared, uncut, atol=1e-5)
    # and the layer's own forward, every expert held, is the uncut layer
    np.testing.assert_allclose(mlp(paddle.to_tensor(h)).value, uncut,
                               atol=1e-5)
    mlp.last_counts = None


# ------------------------------------------------------------ the engines
def _drive_serially(eng):
    """Every decode step read before the next launch (the order of
    ``tests/test_serving.py``'s helper of the same name)."""
    while eng.scheduler.depth or eng.active_slots:
        eng.step()
        launched, eng._in_flight = eng._in_flight, None
        if launched is not None:
            eng._emit(launched, eng._read(launched))


@pytest.mark.parametrize("engine_cls, sampled", [
    (PagedServingEngine, False), (ServingEngine, False),
    (ServingEngine, True)], ids=["paged", "slab", "slab-sampled"])
def test_engines_reproduce_generate_and_the_reference(toy_share, engine_cls,
                                                      sampled):
    """Through the engine as served (bucketed prefill, adoption into
    pages and rows, decode over every row, every first token fed to
    its row's first step on the device, the paged engine's second and
    third request admitted with a step in flight): the
    token streams of ``generate()``, every served token the
    reference's top logit; sampled, the streams of the serial order."""
    net, cfg, w = toy_share
    prompts = [_ids(9, 7).tolist(), _ids(9, 8).tolist(),
               _ids(9, 9).tolist()]
    kw = {"page_size": 8} if engine_cls is PagedServingEngine else {}
    if sampled:
        kw.update(do_sample=True, temperature=0.8, top_k=8, seed=3)

    def run(drive):
        eng = engine_cls(net, max_batch_size=2, max_seq_len=48,
                         min_bucket=16, cache_dtype="float32", **kw)
        # two rows for three requests: the paged engine admits the
        # third into a freed row while the other row has a step in flight
        handles = [eng.submit(p, 6) for p in prompts]
        drive(eng)
        rep = eng.metrics.report()
        eng.close()
        return handles, rep

    handles, rep = run(lambda eng: eng.run_until_idle())
    # every launch but the first of a busy stretch had a step in
    # flight, the one after the third request's admission too (the
    # paged engine admits one an iteration; the slab engine admits the
    # first two at once, they end together and the third finds it idle)
    stretches = 1 + (engine_cls is ServingEngine)
    assert rep["counters"]["steps_overlapped"] \
        == rep["resident_tokens"]["count"] - stretches > 0
    assert all(layer.mlp.last_counts is None for layer in net.model.layers)
    if sampled:
        serial, rep_s = run(_drive_serially)
        assert rep_s["counters"]["steps_overlapped"] == 0
        assert [h.tokens for h in handles] == [h.tokens for h in serial]
        assert all(len(h.tokens) == 6 for h in handles)
        return
    want = np.asarray(net.generate(
        paddle.to_tensor(np.asarray(prompts)), max_new_tokens=6,
        cache_dtype="float32").value)[:, 9:]
    for p, h, stream in zip(prompts, handles, want):
        assert h.tokens == stream.tolist()
        gaps = ref.served_token_gaps(w, cfg, p, h.tokens, 16)
        assert gaps.max() < 1e-4, gaps


def test_a_slot_served_twice_gives_what_a_fresh_engine_gives(toy):
    """One row: the second request lands in the row the first one left,
    whose state nothing cleared, and gets the tokens a fresh engine
    gives it."""
    net = toy[0]
    first, second = _ids(12, 11).tolist(), _ids(7, 12).tolist()
    make = lambda: PagedServingEngine(
        net, max_batch_size=1, max_seq_len=48, page_size=8, min_bucket=16,
        cache_dtype="float32")
    eng = make()
    eng.generate([first], max_new_tokens=5)
    again = eng.generate([second], max_new_tokens=5)[0].tokens
    eng.close()
    fresh = make()
    want = fresh.generate([second], max_new_tokens=5)[0].tokens
    fresh.close()
    assert again == want


def test_step_counters_equal_a_recount(toy_share):
    """``experts_touched`` (held experts with a token) and
    ``local_assignments`` (assignments on held experts) of a decode
    step, summed over the layers, against numpy on the same routing."""
    net = toy_share[0]
    cfg = net.config
    h = paddle.to_tensor(np.random.default_rng(8).normal(
        size=(1, 5, cfg.hidden_size)).astype(np.float32))
    for layer in net.model.layers:
        layer.mlp(h)
    chosen = [np.asarray(layer.mlp.route(h.reshape([5, -1]))[0])
              for layer in net.model.layers]
    got = net.pop_step_counters()
    lo, hi = cfg.experts_first, cfg.experts_first + cfg.held
    here = [(c >= lo) & (c < hi) for c in chosen]
    assert int(got["local_assignments"]) == sum(int(m.sum()) for m in here)
    assert int(got["experts_touched"]) == sum(
        len(np.unique(c[m])) for c, m in zip(chosen, here))
    # 5 tokens x top-4 = 20 sorted rows a layer: under the floor, one rung
    assert int(got["dispatch_rows"]) == 20 * len(chosen)
    assert net.pop_step_counters() == {}
    # and the engine keeps both, a sample a decode step
    eng = PagedServingEngine(net, max_batch_size=2, max_seq_len=32,
                             page_size=8, min_bucket=16,
                             cache_dtype="float32")
    eng.generate([_ids(5, 1).tolist()], max_new_tokens=4)
    rep = eng.metrics.report()
    eng.close()
    steps = rep["local_assignments"]["count"]
    assert steps == rep["experts_touched"]["count"] >= 3
    # 2 rows x top-4 = 8 sorted rows a layer: the one rung, 4 layers
    assert rep["dispatch_rows"]["count"] == steps
    assert rep["dispatch_rows"]["mean"] == 8 * 4
    # 2 rows x top-4 x 4 layers a step, a quarter of the experts held
    assert 0 <= rep["local_assignments"]["max"] <= 2 * 4 * 4
    assert rep["experts_touched"]["max"] <= 4 * cfg.held


@pytest.fixture
def low_floor(monkeypatch):
    """The row ladder without its floor, so that a toy dispatch of 8 or
    64 sorted rows has three rungs as the benchmark cell's 512 have."""
    monkeypatch.setattr(xing4, "_ROW_FLOOR", 1)


def test_dispatch_rows_equal_the_rungs_the_routing_implies(toy_share,
                                                           low_floor):
    """``dispatch_rows`` of a step, summed over the layers, against the
    ladder applied to a numpy recount of each layer's local assignments
    (44 tokens x top-4 = 176 sorted rows a layer: rungs 44, 88, 176)."""
    net = toy_share[0]
    cfg = net.config
    h = paddle.to_tensor(np.random.default_rng(9).normal(
        size=(1, 44, cfg.hidden_size)).astype(np.float32))
    want = []
    for layer in net.model.layers:
        layer.mlp(h)
        local = np.asarray(layer.mlp.route(h.reshape([44, -1]))[0]) \
            - cfg.experts_first
        n_local = int(((local >= 0) & (local < cfg.held)).sum())
        want.append(min(r for r in (44, 88, 176) if r >= n_local))
    got = net.pop_step_counters()
    assert xing4.row_ladder(176) == (44, 88, 176)
    assert int(got["dispatch_rows"]) == sum(want) < 4 * 176


@pytest.mark.parametrize("share", ["a_share", "all_experts"])
def test_an_engine_over_a_laddered_dispatch_serves_the_same_tokens(
        toy, toy_share, share, monkeypatch):
    """The paged engine with the ladder engaged in its prefill (16 x 4
    sorted rows) and decode (2 x 4) programs serves token for token what
    it serves over the one full-length rung, and counts the rows it ran:
    every row where every expert is held, fewer where a quarter are."""
    net = (toy_share if share == "a_share" else toy)[0]
    prompts = [_ids(9, 7).tolist(), _ids(9, 8).tolist(), _ids(9, 9).tolist()]

    def serve():
        eng = PagedServingEngine(net, max_batch_size=2, max_seq_len=48,
                                 page_size=8, min_bucket=16,
                                 cache_dtype="float32")
        tokens = [h.tokens for h in eng.generate(prompts, max_new_tokens=6)]
        rep = eng.metrics.report()
        eng.close()
        return tokens, rep

    want, whole = serve()
    assert whole["dispatch_rows"]["mean"] == 8 * 4
    monkeypatch.setattr(xing4, "_ROW_FLOOR", 1)
    assert xing4.row_ladder(8) == (2, 4, 8)
    got, rep = serve()
    assert got == want
    rows = rep["dispatch_rows"]
    assert rows["count"] == rep["local_assignments"]["count"]
    if share == "all_experts":
        assert rows["mean"] == 8 * 4
    else:
        # rungs 2, 4, 8 in each of 4 layers
        assert 2 * 4 <= rows["min"] <= rows["mean"] < 8 * 4
        assert rows["max"] >= rep["local_assignments"]["max"]


@pytest.mark.parametrize("option, why", [
    ({"cache_dtype": "int8"}, "int8 cache storage is not supported"),
    ({"prefix_cache": True}, "snapshot the state at page boundaries"),
    ({"prefix_cache": True}, "chunked prefill of a tail"),
    ({"prefix_cache": True, "kv_tiering": True}, "KV tiering is not"),
    ({"prefill_transport": object()}, "carries pages and no row state"),
    ({"speculative": object()}, "roll the row's state back")])
def test_options_a_row_state_cannot_serve_are_refused(toy, option, why):
    with pytest.raises(ValueError, match="keeps a state a row") as err:
        PagedServingEngine(toy[0], max_batch_size=2, max_seq_len=32,
                           page_size=8, min_bucket=16, **option)
    assert why in str(err.value)


def test_programs_of_a_row_state_net_have_names_of_their_own(toy):
    from paddle_tpu.serving.engine import build_prefill_body

    assert build_prefill_body(toy[0], False, 0, 1.0).__name__ == \
        "prefill_state_body"
    eng = PagedServingEngine(toy[0], max_batch_size=2, max_seq_len=32,
                             page_size=8, min_bucket=16)
    assert "adopt_state_body" in str(eng._adopt_fn(16))
    sig = eng._program_signature("decode")["model"]
    assert sig["rows"][1][0] == [[4, 16, 16], "float32"]
    eng.close()
    llama = paddle.models.LlamaForCausalLM(LlamaConfig.tiny())
    assert build_prefill_body(llama, False, 0, 1.0).__name__ == \
        "prefill_body"
    eng = PagedServingEngine(llama, max_batch_size=2, max_seq_len=32,
                             page_size=8, min_bucket=16)
    assert "adopt_state_body" not in str(eng._adopt_fn(16))
    assert "rows" not in eng._program_signature("decode")["model"]
    eng.close()
