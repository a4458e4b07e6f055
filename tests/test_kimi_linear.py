"""Kimi-Linear decoder (KDA layers whose state is kept a row, every
fourth layer NoPE latent attention whose latent is kept a token, a
leading dense layer, sigmoid-routed experts of which a share is held)
at a toy size on the CPU, float32: the program against the plain
reference in ``benchmarks/reference/``, through the model's own
forward, through both kinds of cache and through the serving engines.
The parts it is assembled from have their own tests
(``test_solar_open2.py``: the KDA forms and the held-share dispatch;
``test_xing4.py``: MLA and the router); here is what the assembly and
the parts' generalisation add."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (
    KimiLinearConfig,
    KimiLinearForCausalLM,
    Xing4Config,
    Xing4ForCausalLM,
)
from paddle_tpu.models import generation, xing4
from paddle_tpu.quantization import kv as qkv
from paddle_tpu.serving import PagedServingEngine, ServingEngine
from paddle_tpu.serving.paged_pool import PagedKVPool

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.reference import kda_mla_moe_decoder as ref  # noqa: E402

KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "linear_attn_config",
        "num_experts_per_token", "routed_scaling_factor", "moe_renormalize",
        "rms_norm_eps")


def ref_config(cfg):
    """The program's config as the reference's dict (a configuration
    file's keys: ``num_experts`` counts the experts held)."""
    out = {k: getattr(cfg, k) for k in KEYS}
    out.update(num_experts=cfg.held, experts_first=cfg.experts_first,
               published={"num_experts": cfg.num_experts})
    return out


def build(seed=0, **kw):
    """A toy net (hidden 64; layers KDA+dense, KDA, KDA, MLA, KDA; KDA 4
    heads of 16, chunks of 8; MLA 4 heads, latent 16 + 8; 16 experts
    top-4 + shared) with the program's own seeded initializers, its
    config as the reference's dict and its weights by name."""
    paddle.seed(seed)
    cfg = KimiLinearConfig.tiny(**kw)
    net = KimiLinearForCausalLM(cfg)
    net.eval()
    return net, ref_config(cfg), \
        {k: p.value for k, p in net.named_parameters()}


@pytest.fixture(scope="module")
def toy():
    return build()


@pytest.fixture(scope="module")
def toy_share():
    """Experts 8..15 of 16 held: the second half."""
    return build(experts_first=8, experts_held=8)


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


# ------------------------------------------------------------ whole model
@pytest.mark.parametrize("which", ["all_experts", "a_share"])
def test_logits_match_the_reference(which, toy, toy_share):
    net, cfg, w = toy if which == "all_experts" else toy_share
    ids = _ids(21, 1)
    got = _forward(net, ids[None])[0]
    want = ref.logits(w, cfg, jnp.asarray(ids))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_layers_take_mixer_and_ffn_from_the_config():
    """The published lists number the layers from 1: 27 is a
    full-attention layer of a 27-layer model, layer 1 is dense."""
    cfg = KimiLinearConfig()
    mla = [i for i in range(cfg.num_hidden_layers) if cfg.is_mla(i)]
    assert mla == [3, 7, 11, 15, 19, 23, 26]
    assert [i for i in range(27) if cfg.is_dense(i)] == [0]
    assert (cfg.cache_dim, cfg.latent_dim) == (640, 576)
    assert cfg.softmax_scale == 192 ** -0.5
    net = build()[0]
    assert [(type(layer.mixer).__name__, type(layer.mlp).__name__)
            for layer in net.model.layers] == [
        ("SolarOpen2KDA", "Xing4MLP"), ("SolarOpen2KDA", "SolarOpen2MoE"),
        ("SolarOpen2KDA", "SolarOpen2MoE"),
        ("Xing4Attention", "SolarOpen2MoE"),
        ("SolarOpen2KDA", "SolarOpen2MoE")]
    # no q-LoRA: one query projection, no norm
    names = {k for k, _ in net.model.layers[3].mixer.named_parameters()}
    assert "q_proj.weight" in names
    assert not any(n.startswith("q_a_") or n.startswith("q_b_")
                   for n in names)


@pytest.mark.parametrize("kw, says", [
    ({"mla_use_nope": False}, "without rope"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "without rope"),
    ({"linear_attn_config": {
        "full_attn_layers": [4], "kda_layers": [1, 2, 3, 4, 5],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4}},
     "layer 4 is in both"),
    ({"experts_first": 12, "experts_held": 8}, "are not among 16")])
def test_a_config_the_program_was_not_written_for_is_refused(kw, says):
    with pytest.raises(ValueError, match=says):
        KimiLinearConfig.tiny(**kw)


# ------------------------------------------------- the generalised parts
def _mla_inputs(b, s, cfg, seed=0):
    r = np.random.default_rng(seed)
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    return (f(b, s, cfg.num_attention_heads, dq), f(b, s, cfg.kv_lora_rank),
            f(b, s, cfg.qk_rope_head_dim),
            f(cfg.kv_lora_rank, cfg.num_attention_heads
              * (cfg.qk_nope_head_dim + cfg.v_head_dim)) * 0.2)


@pytest.mark.parametrize("absorbed", [False, True])
def test_mla_core_without_rope_is_rope_by_the_identity(absorbed):
    """No ``cos``/``sin`` is ``cos`` 1 and ``sin`` 0: the dims go
    through as they are, on both attention paths, without and with a
    cache."""
    cfg = KimiLinearConfig.tiny()
    q, ckv, k_pe, w = _mla_inputs(2, 6, cfg)
    one = jnp.ones((1, 6, cfg.qk_rope_head_dim // 2), jnp.float32)
    kw = dict(cfg=cfg, absorbed=absorbed)
    bare, _ = xing4.mla_core(q, ckv, k_pe, w, None, None, **kw)
    turned, _ = xing4.mla_core(q, ckv, k_pe, w, one, 0 * one, **kw)
    np.testing.assert_array_equal(bare, turned)
    slab = jnp.zeros((2, 8, cfg.cache_dim), jnp.float32)
    pos = jnp.int32(0)
    bare, c0 = xing4.mla_core(q, ckv, k_pe, w, None, None, cache=slab,
                              pos=pos, **kw)
    turned, c1 = xing4.mla_core(q, ckv, k_pe, w, one, 0 * one, cache=slab,
                                pos=pos, **kw)
    np.testing.assert_array_equal(bare, turned)
    np.testing.assert_array_equal(c0, c1)
    # the cached token is [ckv | k_pe], zero behind it
    np.testing.assert_array_equal(
        c0[:, :6, :cfg.latent_dim], jnp.concatenate([ckv, k_pe], -1))
    assert not np.asarray(c0[..., cfg.latent_dim:]).any()


def test_nope_mla_absorbed_equals_materialised():
    cfg = KimiLinearConfig.tiny()
    q, ckv, k_pe, w = _mla_inputs(2, 9, cfg, 1)
    run = lambda absorbed: xing4.mla_core(
        q, ckv, k_pe, w, None, None, cfg=cfg, absorbed=absorbed)[0]
    np.testing.assert_allclose(run(True), run(False), atol=2e-6)


def _mla_core_as_it_stood(q, ckv, k_rope, w_kvb, cos, sin, *, cfg, cache,
                          pos, page_table):
    """``xing4.mla_core`` before rope became optional (PR 33's), for
    the one-token paged step and the block prefill."""
    dn = cfg.qk_nope_head_dim
    s = q.shape[1]
    q_nope = q[..., :dn]
    q_rope = xing4._rope(q[..., dn:], cos[:, :, None], sin[:, :, None])
    latent = jnp.concatenate([ckv, xing4._rope(k_rope, cos, sin)], -1)
    scale = cfg.softmax_scale
    absorbed = s == 1
    attend = xing4.mla_absorbed if absorbed else xing4.mla_materialised
    fresh = latent
    latent = jnp.pad(latent.astype(cache.dtype), (
        (0, 0), (0, 0), (0, cache.shape[-1] - latent.shape[-1])))
    p = jnp.asarray(pos)
    if page_table is not None:
        (cache,), out = qkv.write_and_attend_paged(
            (cache,), (latent,), p, page_table,
            lambda views, mask: attend(q_nope, q_rope, views[0], w_kvb,
                                       mask, scale))
        return out, cache
    (cache,), (view,), cols = qkv.write_and_view((cache,), (latent,), p)
    if p.ndim == 0 and s == cache.shape[1] and not absorbed:
        return attend(q_nope, q_rope, fresh, w_kvb, None, scale), cache
    mask = qkv.position_mask(cols, view.shape[1])
    return attend(q_nope, q_rope, view, w_kvb, mask, scale), cache


@pytest.mark.parametrize("program", ["paged_step", "block_prefill"])
def test_a_roped_mla_core_traces_the_program_it_always_did(program):
    """Xing4's jaxpr: ``mla_core`` given ``cos``/``sin`` against the
    function as it stood, the paged one-token step and the prefill of a
    whole block."""
    cfg = Xing4Config.tiny()
    if program == "paged_step":
        b, s = 3, 1
        cache = jnp.zeros((13, 8, cfg.cache_dim), jnp.bfloat16)
        pos = jnp.asarray([5, 0, 17])
        table = jnp.asarray(1 + np.arange(12).reshape(3, 4), jnp.int32)
    else:
        b, s = 1, 16
        cache = jnp.zeros((1, 16, cfg.cache_dim), jnp.bfloat16)
        pos, table = jnp.int32(0), None
    q, ckv, k_pe, w = _mla_inputs(b, s, cfg, 2)
    half = jnp.ones((b, s, cfg.qk_rope_head_dim // 2), jnp.float32)
    text = lambda fn: str(jax.make_jaxpr(
        lambda q, ckv, k_pe, w, cos, sin, cache, pos: fn(
            q, ckv, k_pe, w, cos, sin, cfg=cfg, cache=cache, pos=pos,
            page_table=table))(q, ckv, k_pe, w, half, half, cache, pos))
    assert text(xing4.mla_core) == text(_mla_core_as_it_stood)


def test_a_q_lora_attention_keeps_its_parameters():
    """Xing4's attention still compresses and norms its query: the
    names and shapes its checkpoints and the benchmark's builder
    know."""
    paddle.seed(0)
    net = Xing4ForCausalLM(Xing4Config.tiny(hc_sinkhorn_iters=2))
    names = {k: tuple(p.value.shape) for k, p in
             net.model.layers[0].self_attn.named_parameters()}
    assert names["q_a_proj.weight"] == (64, 24)
    assert names["q_a_layernorm.weight"] == (24,)
    assert names["q_b_proj.weight"] == (24, 4 * 24)
    assert "q_proj.weight" not in names


# ------------------------------------------------------------ the caches
def test_cache_statement_names_a_latent_page_and_row_arrays(toy):
    net = toy[0]
    cfg, ps = net.config, 8
    kept = (((4, 16, 16), "float32"), ((3, 192), None))
    assert generation.cache_layout(cfg) == [(), (), (), ((128,),), ()]
    assert generation.row_layout(cfg) == [kept, kept, kept, (), kept]
    assert generation.keeps_row_state(cfg)
    assert not generation.keeps_kv_pairs(cfg)
    assert not generation.token_arrays_are_kv_pairs(cfg)
    assert generation.row_array_mask(cfg) == \
        [True] * 6 + [False] + [True] * 2
    # the published sizes: 1280 B a token in one layer of four, 8.68 MB
    # a row over the four KDA layers of the benchmark's depth
    full = KimiLinearConfig(num_hidden_layers=5)
    assert generation.cache_token_bytes(full, "bfloat16") == 1280
    assert generation.cache_row_bytes(full, "bfloat16") == \
        4 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 8683520

    pool = PagedKVPool(cfg, page_size=ps, num_pages=5, dtype="bfloat16",
                       max_seq_len=32)
    # page accounting counts the MLA layer's latent alone
    assert pool.page_bytes() == ps * 128 * 2
    assert pool.row_bytes() == 4 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    arena = pool.alloc_arena_arrays(rows=3)
    assert [a.shape for a in arena[3]] == [(6, ps, 128)]
    for i in (0, 1, 2, 4):
        assert [(a.shape, a.dtype) for a in arena[i]] == [
            ((3, 4, 16, 16), jnp.float32), ((3, 3, 192), jnp.bfloat16)]
    with pytest.raises(ValueError, match="keeps another cache layout"):
        generation.alloc_kv_caches(cfg, 2, 16, "int8")


@pytest.mark.parametrize("cache", ["slab", "paged"])
def test_prefill_then_decode_gives_the_reference_at_every_position(
        toy_share, cache):
    """A right-padded bucketed prefill, then one-token steps through a
    slab (rows at their own positions) or through latent pages with the
    row state beside them, teacher-forced: every logits row against the
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
        # what the engine's adopt_state_body does, in one pass: the
        # block's latent into pages, its state and tail into the row
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


def test_padded_bucket_leaves_what_the_unpadded_prompt_leaves(toy):
    """State, tail and latent after a right-padded bucket are those the
    unpadded prompt leaves; state and tail bitwise the same whatever
    the pad tokens are (the scan freezes them at ``length``), the
    latent the same at every position a decode step may read."""
    net = toy[0]
    n, bucket = 11, 16
    ids = _ids(n, 6)
    by_row = generation.row_array_mask(net.config)

    def run(tokens, length):
        block = generation.alloc_kv_caches(net.config, 1, len(tokens),
                                           "float32")
        _, block = jax.jit(lambda i, c: generation.prefill(
            net, i, c, length=length))(jnp.asarray(tokens)[None], block)
        return [np.asarray(a) for layer in block for a in layer]

    def padded(pad):
        out = np.full((bucket,), pad, np.int64)
        out[:n] = ids
        return out

    bare = run(ids, None)
    zeros, other = run(padded(0), n), run(padded(255), n)
    for a, b, c, is_row in zip(zeros, other, bare, by_row):
        if is_row:
            assert np.array_equal(a, b)
            np.testing.assert_allclose(a, c, rtol=0, atol=5e-6)
        else:
            assert np.array_equal(a[:, :n], b[:, :n])
            np.testing.assert_allclose(a[:, :n], c, rtol=0, atol=5e-6)
    # and without ``length`` the pad tokens DO move the state
    moved = run(padded(0), None)
    assert np.abs(moved[0] - bare[0]).max() > 1e-3


@pytest.mark.parametrize("planted", [None, "next_row", "no_pages"])
def test_what_the_engines_admission_leaves_a_row_is_the_references(
        toy, planted):
    """The paged engine's own prefill, page claim and adoption program
    (the latent block scattered into pages, state and tail copied into
    the row at once), read back through the row's page table as the
    benchmark's check reads it: the reference's stored latent, final
    states and convolution tails of a prompt that does not fill its
    bucket. A state adopted into the next row, or a latent scattered
    into no page of the row, reads as wrong as can be."""
    from benchmarks.models import kda_mla_moe_decoder as builder

    net, cfg, weights = toy
    ids = _ids(27, 8)
    spec = dict(max_batch_size=2, max_seq_len=64, page_size=8, min_bucket=16,
                cache_dtype="float32")
    plant = {
        None: None,
        "next_row": lambda eng, arena, block, pages, row, *feed: (
            arena, block, pages, (row + 1) % 2, *feed),
        "no_pages": lambda eng, arena, block, pages, row, *feed: (
            arena, block, jnp.zeros_like(pages), row, *feed)}[planted]
    left = builder.adopted_by_engine(net, spec, ids, plant)
    states = {}
    ref.hidden(weights, cfg, jnp.asarray(ids), None, states)
    st = ref.mixer_static(cfg)
    for i, (state, mixer_in) in states.items():
        w = ref.layer_weights(weights, f"model.layers.{i}.mixer.")
        if state is None:
            want = np.asarray(ref.mla_latent(mixer_in, w, eps=st["eps"]))
            err = ref.relative_errors(left[i][0][:, :want.shape[1]], want)
            assert left[i][0].shape == (27, net.config.cache_dim)
            assert (err.max() < 2e-5) if planted != "no_pages" \
                else (err.min() == 1.0)
            continue
        err = np.concatenate([
            ref.state_errors(left[i][0], np.asarray(state)),
            ref.relative_errors(left[i][1], np.asarray(ref.kda_tail(
                mixer_in, w)))])
        assert (err.max() < 2e-5) if planted != "next_row" \
            else (err.min() == 1.0)


def test_the_reference_rounds_the_latent_where_it_is_told_to(toy):
    """``rounded``: float32 twice rounds nothing; bfloat16 moves the
    mixer's output by a bfloat16 rounding and no more."""
    _, cfg, weights = toy
    w = ref.layer_weights(weights, "model.layers.3.mixer.")
    st = {k: v for k, v in ref.mixer_static(cfg).items()
          if k in ("heads", "dn", "dr", "dv", "eps")}
    x = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    plain = np.asarray(ref.mla_mixer(x, w, **st))
    same = np.asarray(ref.mla_mixer(x, w, rounded=("float32", "float32"),
                                    **st))
    assert np.array_equal(plain, same)
    err = ref.relative_errors(np.asarray(ref.mla_mixer(
        x, w, rounded=("bfloat16", "bfloat16"), **st)), plain)
    assert 1e-4 < err.max() < 2e-2


# --------------------------------------------------------------- experts
def test_both_halves_and_the_dense_layer_add_up_to_the_uncut_layers(toy):
    """The routed parts the two halves (first 0 and 8 of 16) compute,
    plus the shared expert ONCE, equal the uncut reference layer, in
    the program and in the reference; the dense layer is the
    reference's SwiGLU."""
    net, cfg, w = toy
    mlp = net.model.layers[1].mlp
    lw = ref.layer_weights(w, "model.layers.1.mlp.")
    h = jnp.asarray(np.random.default_rng(4).normal(size=(13, 64)),
                    jnp.float32)
    moe = ref.moe_static(cfg)
    routed, shared, chosen, _ = ref.expert_ffn(h, lw, moe=moe, share=(0, 16))
    uncut = np.asarray(routed + shared)
    idx, wts = mlp.route(paddle.to_tensor(h))
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(chosen), -1))
    # the weights sum to routed_scaling_factor over all k
    np.testing.assert_allclose(np.asarray(wts.value).sum(-1), 2.446,
                               rtol=1e-6)
    program, reference = np.zeros_like(uncut), np.zeros_like(uncut)
    for first in (0, 8):
        cut = dict(lw, experts_gate_up=lw["experts_gate_up"][first:first + 8],
                   experts_down=lw["experts_down"][first:first + 8])
        part, _, _, _ = ref.expert_ffn(h, cut, moe=moe, share=(first, 8))
        reference += np.asarray(part)
        program += np.asarray(xing4.moe_dispatch(
            h, idx, wts.value, cut["experts_gate_up"], cut["experts_down"],
            first=first, held=8))
    shared = np.asarray(shared)
    np.testing.assert_allclose(reference + shared, uncut, atol=1e-5)
    np.testing.assert_allclose(program + shared, uncut, atol=1e-5)
    np.testing.assert_allclose(mlp(paddle.to_tensor(h)).value, uncut,
                               atol=1e-5)
    mlp.last_counts = None
    dense = ref.layer_weights(w, "model.layers.0.mlp.")
    np.testing.assert_allclose(
        net.model.layers[0].mlp(paddle.to_tensor(h)).value,
        ref._swiglu(h, dense["gate_up_proj.weight"],
                    dense["down_proj.weight"]), atol=1e-5)


def test_step_counters_count_the_expert_layers_only(toy_share):
    """Four of the five layers route: the dense layer counts nothing,
    and the sums are a numpy recount over the other four."""
    net = toy_share[0]
    cfg = net.config
    h = paddle.to_tensor(np.random.default_rng(8).normal(
        size=(1, 5, cfg.hidden_size)).astype(np.float32))
    routed = [layer.mlp for layer in net.model.layers
              if hasattr(layer.mlp, "route")]
    assert len(routed) == 4
    for layer in net.model.layers:
        layer.mlp(h)
    chosen = [np.asarray(m.route(h.reshape([5, -1]))[0]) for m in routed]
    got = net.pop_step_counters()
    lo, hi = cfg.experts_first, cfg.experts_first + cfg.held
    here = [(c >= lo) & (c < hi) for c in chosen]
    assert int(got["local_assignments"]) == sum(int(m.sum()) for m in here)
    assert int(got["experts_touched"]) == sum(
        len(np.unique(c[m])) for c, m in zip(chosen, here))
    # 5 tokens x top-4 = 20 sorted rows a layer: one rung, 4 layers
    assert int(got["dispatch_rows"]) == 20 * 4
    assert net.pop_step_counters() == {}


# ------------------------------------------------------------ the engines
@pytest.mark.parametrize("engine_cls", [PagedServingEngine, ServingEngine])
def test_engines_reproduce_generate_and_the_reference(toy_share, engine_cls):
    """Through the engine as served (bucketed prefill handed
    ``length``, adoption of the latent into pages and of state and tail
    into the row in one program, decode over every row, admissions with
    a step in flight): the token streams of ``generate()``, every
    served token the reference's top logit."""
    net, cfg, w = toy_share
    prompts = [_ids(9, 7).tolist(), _ids(9, 8).tolist(),
               _ids(9, 9).tolist()]
    kw = {"page_size": 8} if engine_cls is PagedServingEngine else {}
    eng = engine_cls(net, max_batch_size=2, max_seq_len=48, min_bucket=16,
                     cache_dtype="float32", **kw)
    handles = eng.generate(prompts, max_new_tokens=6)
    rep = eng.metrics.report()
    eng.close()
    # every launch but the first of a busy stretch had a step in
    # flight, the one after the third request's admission too (the
    # paged engine admits one an iteration; the slab engine admits the
    # first two at once, they end together and the third finds it idle)
    stretches = 1 + (engine_cls is ServingEngine)
    assert rep["counters"]["steps_overlapped"] \
        == rep["resident_tokens"]["count"] - stretches > 0
    assert all(getattr(layer.mlp, "last_counts", None) is None
               for layer in net.model.layers)
    # 2 rows x top-4 x 4 expert layers a step, half the experts held
    assert rep["local_assignments"]["count"] == \
        rep["dispatch_rows"]["count"] >= 3
    assert rep["dispatch_rows"]["mean"] == 8 * 4
    assert 0 < rep["local_assignments"]["max"] <= 8 * 4
    assert rep["experts_touched"]["max"] <= 4 * cfg["num_experts"]
    want = np.asarray(net.generate(
        paddle.to_tensor(np.asarray(prompts)), max_new_tokens=6,
        cache_dtype="float32").value)[:, 9:]
    for p, h, stream in zip(prompts, handles, want):
        assert h.tokens == stream.tolist()
        gaps = ref.served_token_gaps(w, cfg, p, h.tokens, 16)
        assert gaps.max() < 1e-4, gaps


def test_a_slot_served_twice_gives_what_a_fresh_engine_gives(toy):
    """One row: the second request lands in the row the first one left,
    whose state nothing cleared and whose pages went back to the pool,
    and gets the tokens a fresh engine gives it."""
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


LATENT = "states a cache that is not K and V per head"


@pytest.mark.parametrize("option, why", [
    ({"cache_dtype": "int8"}, "int8 cache storage is not supported"),
    ({"cache_dtype": "int8"}, LATENT + "; int8 cache storage is written"),
    ({"prefix_cache": True}, "snapshot the state at page boundaries"),
    ({"prefix_cache": True}, LATENT + "; the prefix cache is written"),
    ({"prefix_cache": True, "kv_tiering": True}, "KV tiering is not"),
    ({"prefix_cache": True, "kv_tiering": True},
     LATENT + "; the prefix cache, KV tiering are written"),
    ({"prefill_transport": object()}, "carries pages and no row state"),
    ({"prefill_transport": object()}, LATENT + "; remote prefill is"),
    ({"speculative": object()}, "roll the row's state back"),
    ({"speculative": object()}, LATENT + "; speculative decoding is")])
def test_options_neither_cache_can_serve_are_refused_with_both_reasons(
        toy, option, why):
    """The net states a latent page AND a row state: each option is
    refused in the sentence the row state has for it and in the
    sentence the latent page has."""
    with pytest.raises(ValueError, match="keeps a state a row") as err:
        PagedServingEngine(toy[0], max_batch_size=2, max_seq_len=32,
                           page_size=8, min_bucket=16, **option)
    assert why in str(err.value)


def test_a_layer_that_keeps_nothing_at_all_is_refused_too():
    """K and V pairs in every layer but one that keeps nothing a token
    and nothing a row: neither reason applies, the option is refused
    all the same, in the latent page's sentence."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    net = LlamaForCausalLM(LlamaConfig.tiny())
    pairs = generation.cache_layout(net.config)
    net.config.cache_layout = lambda: [()] + pairs[1:]
    assert generation.token_arrays_are_kv_pairs(net.config)
    assert not generation.keeps_row_state(net.config)
    with pytest.raises(ValueError, match=LATENT + "; int8 cache storage"):
        PagedServingEngine(net, max_batch_size=2, max_seq_len=32,
                           page_size=8, min_bucket=16, cache_dtype="int8")


def test_programs_have_the_names_the_trace_readers_know(toy):
    from paddle_tpu.serving.engine import build_prefill_body

    assert build_prefill_body(toy[0], False, 0, 1.0).__name__ == \
        "prefill_state_body"
    eng = PagedServingEngine(toy[0], max_batch_size=2, max_seq_len=32,
                             page_size=8, min_bucket=16)
    assert "adopt_state_body" in str(eng._adopt_fn(16))
    sig = eng._program_signature("decode")["model"]
    assert sig["rows"][0][0] == [[4, 16, 16], "float32"]
    assert sig["rows"][3] == []
    text = eng._decode_fn.lower(*eng._decode_example_args()).as_text(
        debug_info=True)
    eng.close()
    for scope in ("attn_core", "kda_proj", "kda_conv", "kda_gate",
                  "kda_step", "moe_router", "moe_experts", "shared_expert",
                  "mlp/gate_up_proj"):
        assert scope in text, scope
