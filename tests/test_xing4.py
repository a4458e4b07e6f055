"""Xing4.0 decoder (latent attention, dropless sigmoid-routed experts,
mHC residual streams, MTP) at a toy size on the CPU, float32: the
program against the plain reference in ``benchmarks/reference/``,
through the model's own forward and through the serving engines, and
the cache statement the pools allocate from."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, Xing4Config, Xing4ForCausalLM
from paddle_tpu.models import generation, xing4
from paddle_tpu.quantization import kv as qkv
from paddle_tpu.serving import PagedServingEngine, ServingEngine
from paddle_tpu.serving.paged_pool import PagedKVPool

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.reference import latent_moe_decoder as ref  # noqa: E402

KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta", "rope_scaling")


def build(seed=0, **kw):
    """A toy net (hidden 64, 4 heads, latent 16 + 8 rope dims, 8
    experts top-2 + shared, 1 dense + 2 expert layers) with the
    program's own seeded initializers, its config as the reference's
    dict and its weights by name."""
    paddle.seed(seed)
    cfg = Xing4Config.tiny(**kw)
    net = Xing4ForCausalLM(cfg)
    net.eval()
    # a selection bias that matters: the choice and the weights differ
    for name, p in net.named_parameters():
        if name.endswith("e_bias"):
            p.value = 0.05 * jax.random.normal(
                jax.random.key(len(name)), p.value.shape, jnp.float32)
    return net, {k: getattr(cfg, k) for k in KEYS}, \
        {k: p.value for k, p in net.named_parameters()}


@pytest.fixture(scope="module")
def toy():
    # eight Sinkhorn iterations unroll to a program that compiles in
    # half the time; the published twenty have a test of their own
    return build(hc_sinkhorn_iters=8)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, (n,))


def _jitted(net, method="forward"):
    """The net's eager forward as one compiled program (op by op it
    costs a dispatch an op); weights are constants of it."""
    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor

    def run(ids):
        with tape.trace_scope(), tape.no_grad():
            return getattr(net, method)(Tensor(ids)).value

    return jax.jit(run)


@pytest.mark.parametrize("hc_mult", [2, 3])
def test_logits_match_the_reference(hc_mult):
    net, cfg, w = build(seed=hc_mult, hc_mult=hc_mult)
    ids = _ids(16, hc_mult)
    got = np.asarray(_jitted(net)(ids[None]))[0]
    want = np.asarray(ref.logits(w, cfg, jnp.asarray(ids)))
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_mtp_logits_match_the_reference(toy):
    net, cfg, w = toy
    ids = _ids(16, 3)
    got = np.asarray(_jitted(net, "mtp_logits")(ids[None]))[0]
    want = np.asarray(ref.mtp_logits(w, cfg, jnp.asarray(ids)))
    assert got.shape == (15, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the main model's own logits at hc_mult 4
    np.testing.assert_allclose(
        np.asarray(_jitted(net)(ids[None]))[0],
        np.asarray(ref.logits(w, cfg, jnp.asarray(ids))), atol=2e-5)
    with pytest.raises(ValueError, match="num_nextn_predict_layers 0"):
        Xing4ForCausalLM(Xing4Config.tiny(num_nextn_predict_layers=0)) \
            .mtp_logits(paddle.to_tensor(ids[None]))


@pytest.mark.parametrize("case", ["no_cache", "slab_rows", "paged"])
def test_absorbed_attention_equals_materialised(case):
    """One-token steps run absorbed, prompts materialised: the same
    attention either way, with and without a cache."""
    cfg = Xing4Config.tiny()
    rng = np.random.default_rng(1)
    b, s, h = 2, (5 if case == "no_cache" else 1), cfg.num_attention_heads
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    q = f(b, s, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    ckv, k_rope = f(b, s, cfg.kv_lora_rank), f(b, s, cfg.qk_rope_head_dim)
    w_kvb = f(cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    pos = None if case == "no_cache" else jnp.asarray([7, 3])
    net_rope = Xing4ForCausalLM(cfg).model.rope_at
    cos, sin = net_rope(xing4._positions(pos, b, s))
    kw = dict(cfg=cfg, pos=pos)
    if case == "slab_rows":
        kw["cache"] = f(b, 16, cfg.cache_dim)
    elif case == "paged":
        kw["cache"] = f(5, 8, cfg.cache_dim)
        kw["page_table"] = jnp.asarray([[1, 2], [3, 4]])
    outs = [xing4.mla_core(q, ckv, k_rope, w_kvb, cos, sin, absorbed=a, **kw)
            for a in (True, False)]
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=2e-5)
    if case != "no_cache":
        np.testing.assert_array_equal(outs[0][1], outs[1][1])


@pytest.mark.parametrize("routing", ["random", "one_expert", "starved"])
def test_dropless_dispatch_equals_the_expert_loop(routing):
    """Sort + grouped matmul against the reference's loop over every
    expert: with all tokens forced onto one expert, and with an expert
    that gets nothing."""
    rng = np.random.default_rng(2)
    t, k, e, c, i = 12, 2, 8, 64, 32
    h = jnp.asarray(rng.standard_normal((t, c)), jnp.float32)
    gu = jnp.asarray(0.1 * rng.standard_normal((e, c, 2 * i)), jnp.float32)
    dn = jnp.asarray(0.1 * rng.standard_normal((e, i, c)), jnp.float32)
    if routing == "one_expert":
        idx = np.full((t, k), 5)
    else:
        idx = np.stack([rng.permutation(e)[:k] for _ in range(t)])
        if routing == "starved":
            idx = np.where(idx == 2, 7, idx)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    idx = jnp.asarray(idx)
    got = xing4.moe_dispatch(h, idx, w, gu, dn)
    dense = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].add(w)
    want = ref.experts(h, dense, gu, dn)
    np.testing.assert_allclose(got, want, atol=2e-5)
    touched = int(xing4.experts_touched(idx, e))
    assert touched == len(set(np.asarray(idx).ravel().tolist()))
    assert touched == {"one_expert": 1}.get(routing, touched) and (
        routing != "starved" or 2 not in np.asarray(idx))


def test_expert_layer_routes_and_mixes_as_the_reference(toy):
    """The expert layer's module on a given input: ``route`` chooses
    the reference's experts, and ``forward`` (router, dispatch, shared
    expert) gives the reference's ``expert_ffn``."""
    net, cfg, w = toy
    index = cfg["first_k_dense_replace"]
    mlp = net.model.layers[index].mlp
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (24, cfg["hidden_size"])), jnp.float32)
    prefix = f"model.layers.{index}.mlp."
    want, chosen, margin = ref.expert_ffn(
        h, {k[len(prefix):]: v for k, v in w.items()
            if k.startswith(prefix)}, moe=ref.moe_static(cfg))
    idx, weights = mlp.route(paddle.to_tensor(h))
    assert float(margin.min()) > 1e-5
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(chosen, -1))
    assert tuple(weights.shape) == (24, cfg["num_experts_per_tok"])
    np.testing.assert_allclose(mlp(paddle.to_tensor(h)).value, want,
                               atol=2e-5)
    mlp.last_touched = None


@pytest.mark.parametrize("n", [2, 3, 4])
def test_hres_is_doubly_stochastic_and_mixes(n):
    """Rows and columns of ``Hres`` sum to one within the iterations'
    error; under the seeded init it is neither the identity nor
    uniform, so the stream mixing is really exercised."""
    paddle.seed(n)
    cfg = Xing4Config.tiny(hc_mult=n)
    hc = xing4.Xing4HyperConnection(cfg)
    x = jnp.asarray(np.random.default_rng(n).standard_normal(
        (10, n, cfg.hidden_size)), jnp.float32)
    h_pre, h_post, h_res = xing4.hc_maps(
        x, hc.phi.value, hc.bias.value, hc.alpha.value, **hc.kw)
    m = np.moveaxis(np.asarray(h_res), -1, 0)               # [T, n, n]
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-4)
    assert np.abs(m - np.eye(n)).max() > 0.1
    assert np.abs(m - 1.0 / n).max() > 0.05
    assert ((0 < np.asarray(h_pre)) & (np.asarray(h_pre) < 1)).all()
    assert ((0 < np.asarray(h_post)) & (np.asarray(h_post) < 2)).all()
    want = ref.hc_maps(x, hc.phi.value, hc.bias.value, hc.alpha.value,
                       **hc.kw)
    np.testing.assert_allclose(m, want[2], atol=1e-5)


def test_prefill_then_paged_decode_gives_the_reference_logits(toy):
    """The serving programs' own steps by hand: bucketed prefill into a
    block, the block adopted into a page arena, one paged absorbed
    decode step; both logits rows against the reference's full
    forward."""
    net, cfg, w = toy
    n, bucket, ps = 11, 16, 8
    ids = _ids(16, 5)       # the reference's one length; causal
    padded = np.zeros((1, bucket), np.int64)
    padded[0, :n] = ids[:n]
    block = generation.alloc_kv_caches(net.config, 1, bucket, "float32")
    row0, block = jax.jit(lambda i, c: generation.prefill(
        net, i, c, length=n))(jnp.asarray(padded), block)
    pool = PagedKVPool(net.config, page_size=ps, num_pages=6,
                       dtype="float32", max_seq_len=32)
    pages = jnp.asarray([4, 2])
    arena = [tuple(qkv.adopt_into_pages(a, blk, pages, bucket // ps, ps)
                   for a, blk in zip(layer, blk_layer))
             for layer, blk_layer in zip(pool.alloc_arena_arrays(), block)]
    table = jnp.asarray([[4, 2, 5, 0]])
    row1, arena = jax.jit(lambda t, c: generation.decode_step(
        net, t, c, jnp.asarray([n]), page_table=table))(
            jnp.asarray(ids[None, n:n + 1]), arena)
    want = np.asarray(ref.logits(w, cfg, jnp.asarray(ids)))
    np.testing.assert_allclose(row0[0], want[n - 1], atol=2e-5)
    np.testing.assert_allclose(row1[0], want[n], atol=2e-5)


@pytest.mark.parametrize("engine_cls", [PagedServingEngine, ServingEngine])
def test_engines_reproduce_generate_and_the_reference(toy, engine_cls):
    """Through the engine as served (prefill, adopt, decode over every
    row): the token streams of ``generate()``, every served token the
    reference's top logit, and the two histograms of a step's cost."""
    net, cfg, w = toy
    prompts = [_ids(9, 7).tolist(), _ids(9, 8).tolist()]
    kw = {"page_size": 8} if engine_cls is PagedServingEngine else {}
    eng = engine_cls(net, max_batch_size=3, max_seq_len=48, min_bucket=16,
                     cache_dtype="float32", **kw)
    handles = eng.generate(prompts, max_new_tokens=6)
    rep = eng.metrics.report()
    eng.close()
    # no program of the engine left a tracer of its counters on the net
    assert all(getattr(layer.mlp, "last_touched", None) is None
               for layer in net.model.layers)
    want = np.asarray(net.generate(
        paddle.to_tensor(np.asarray(prompts)), max_new_tokens=6,
        cache_dtype="float32").value)[:, 9:]
    for p, h, stream in zip(prompts, handles, want):
        assert h.tokens == stream.tolist()
        gaps = ref.served_token_gaps(w, cfg, p, h.tokens, 16)
        assert gaps.max() < 1e-4, gaps
    # a sample a decode step (the rows may start a step apart); two
    # expert layers of 8 experts, 3 rows x top-2 a step
    steps = rep["experts_touched"]["count"]
    assert 5 <= steps == rep["resident_tokens"]["count"] <= 6
    assert 2 <= rep["experts_touched"]["min"] \
        and rep["experts_touched"]["max"] <= 12
    assert rep["resident_tokens"]["sum"] == 2 * sum(range(9, 14))


def test_cache_statement_sizes_pages_and_arenas(toy):
    """A latent net's page is ONE array a layer, ``latent_dim`` numbers
    a token padded to whole lanes; Llama's stays K and V per head."""
    net = toy[0]
    cfg, ps = net.config, 8
    assert (cfg.latent_dim, cfg.cache_dim) == (24, 128)
    assert Xing4Config().cache_layout() == [((640,),)] * 40
    assert generation.cache_layout(cfg) == [((128,),)] * 3
    assert not generation.keeps_kv_pairs(cfg)
    pool = PagedKVPool(cfg, page_size=ps, num_pages=5, dtype="bfloat16",
                       max_seq_len=32)
    assert pool.page_bytes() == ps * 3 * 128 * 2
    arena = pool.alloc_arena_arrays()
    assert [tuple(a.shape for a in layer) for layer in arena] == \
        [((6, ps, 128),)] * 3 and arena[0][0].dtype == jnp.bfloat16
    slab = generation.alloc_kv_caches(cfg, 2, 16, "float32")
    assert [tuple(a.shape for a in layer) for layer in slab] == \
        [((2, 16, 128),)] * 3

    lcfg = LlamaConfig.tiny(num_key_value_heads=2)
    pair = ((2, 16), (2, 16))
    assert generation.cache_layout(lcfg) == [pair] * 2
    assert generation.keeps_kv_pairs(lcfg)
    for dtype, token in (("bfloat16", 2 * 16 * 2), ("int8", 2 * (16 + 4))):
        lpool = PagedKVPool(lcfg, page_size=ps, num_pages=5, dtype=dtype,
                            max_seq_len=32)
        assert lpool.page_bytes() == 2 * 2 * ps * token
        larena = lpool.alloc_arena_arrays()
        assert len(larena) == 2 and all(len(layer) == 2 for layer in larena)
        assert larena[0][0].shape == (6, ps, 2, 16)


@pytest.mark.parametrize("option", [
    {"cache_dtype": "int8"}, {"prefix_cache": True},
    {"prefix_cache": True, "kv_tiering": True}, {"speculative": object()}])
def test_options_written_for_kv_pairs_are_refused(toy, option):
    with pytest.raises(ValueError, match="not K and V per head"):
        PagedServingEngine(toy[0], max_batch_size=2, max_seq_len=32,
                           page_size=8, min_bucket=16, **option)
    with pytest.raises(ValueError, match="int8 cache storage"):
        generation.alloc_kv_caches(toy[0].config, 1, 16, "int8")
