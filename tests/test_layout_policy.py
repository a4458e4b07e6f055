"""First-class sharding layout policy (parallel.layout) + memory levers.

The tentpole contract: the default ``tp-pp-dp`` LayoutPolicy reproduces
the legacy per-model annotations byte-for-byte (spec table + constructed
TP layers + trained numerics), and the levers riding on the seam hold —
the explicit vocab-parallel CE matches unsharded cross entropy to fp32
tolerance while NEVER materializing a full-vocab fp32 block (pinned on
avals), pp-sharded optimizer state writes moments back sharded over pp
with unchanged training numerics, and the jaxpr linter accepts the
policy's axis names. The full 7B lowerings run in tools/layout_smoke.py
(a make gate); the compiled-pp-ring proofs here use a small config.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet.base.topology import (
    CommunicateTopology,
    HybridCommunicateGroup,
)
from paddle_tpu.distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from paddle_tpu.jit.trainer import CompiledTrainStep
from paddle_tpu.parallel import layout, mesh as mesh_mod, tp_ops

VOCAB, HID, B, S = 32, 16, 4, 6

@pytest.fixture(scope="module")
def hcg():
    topo = CommunicateTopology(
        ["dp", "pp", "sharding", "sep", "mp"], [2, 2, 1, 1, 2]
    )
    return HybridCommunicateGroup(topo)


# ------------------------------------------------------- policy object
def test_default_policy_spec_table_matches_legacy_annotations():
    pol = layout.get_policy()
    assert pol.name == "tp-pp-dp"
    assert tuple(pol.spec("embedding")) == ("mp", None)
    assert tuple(pol.spec("column_weight")) == (None, "mp")
    assert tuple(pol.spec("column_bias")) == ("mp",)
    assert tuple(pol.spec("row_weight")) == ("mp", None)
    assert tuple(pol.spec("replicated")) == ()
    assert tuple(pol.spec("lm_head")) == (None, "mp")
    assert not pol.vocab_parallel_loss
    assert not pol.pp_shard_optimizer_state
    with pytest.raises(KeyError, match="family"):
        pol.spec("nonsense")


def test_registry_resolve_and_scoped_swap():
    assert "pp-sharded-state" in layout.list_policies()
    assert layout.resolve("long-context").use_sep_attention
    with pytest.raises(KeyError, match="unknown layout policy"):
        layout.resolve("no-such-layout")
    before = layout.get_policy().name
    with layout.use_policy("pp-sharded-state") as pol:
        assert pol.pp_shard_optimizer_state
        assert layout.get_policy().name == "pp-sharded-state"
    assert layout.get_policy().name == before


def test_set_policy_restore_keeps_implicit_default():
    """`prev = set_policy(p) ... set_policy(prev)` must restore the
    implicit-default state, not promote it to an installed default —
    policy_installed() gates the linter's extra axis names."""
    assert not layout.policy_installed()
    prev = layout.set_policy("pp-sharded-state")
    try:
        assert prev is None
        assert layout.policy_installed()
    finally:
        layout.set_policy(prev)
    assert not layout.policy_installed()
    assert layout.get_policy().name == "tp-pp-dp"


def test_trainer_applies_captured_policy_outside_context(hcg):
    """The README pattern: construct the trainer inside use_policy,
    step it AFTER the context exits — the captured policy must apply in
    FULL (pp-sharded moments AND the trace-time loss/acc routing)."""
    paddle.seed(13)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    with layout.use_policy("pp-sharded-state"):
        step = CompiledTrainStep(
            net, lambda o, t: F.cross_entropy(o, t), opt
        )
    assert layout.get_policy().name == "tp-pp-dp"  # context exited
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.asarray(rng.randint(0, 8, (8,)))
    loss, _ = step([Tensor(x)], [Tensor(y)])
    assert np.isfinite(float(loss.numpy()))
    mats = {k: v for k, v in opt._accumulators.items()
            if getattr(v, "ndim", 0) > 1}
    assert mats and all(
        "pp" in str(v.sharding.spec) for v in mats.values()
    )


def test_derive_registers_variant():
    pol = layout.derive("tp-pp-dp", "test-variant",
                        vocab_parallel_loss=True)
    try:
        assert layout.resolve("test-variant") is pol
        assert pol.vocab_parallel_loss
        # base is untouched (policies are frozen values)
        assert not layout.resolve("tp-pp-dp").vocab_parallel_loss
    finally:
        layout._POLICIES.pop("test-variant", None)


def test_pp_extend_spec_rules(hcg):
    pol = layout.PP_SHARDED_STATE
    # first unsharded pp-divisible dim takes the pp axis
    assert tuple(pol.pp_extend_spec(P(None, "mp"), (8, 4))) == \
        ("pp", "mp")
    assert tuple(pol.pp_extend_spec(P("mp", None), (8, 4))) == \
        ("mp", "pp")
    assert tuple(pol.pp_extend_spec(P(), (6,))) == ("pp",)
    # indivisible dims are skipped; nothing eligible -> None
    assert pol.pp_extend_spec(P(), (3,)) is None
    assert tuple(pol.pp_extend_spec(P("mp", None), (3, 4))) == \
        ("mp", "pp")
    # already pp-sharded leaves stay put (steady-state idempotence)
    assert pol.pp_extend_spec(P("pp", "mp"), (8, 4)) is None


def test_optimizer_state_sharding_respects_lever(hcg):
    v = jax.ShapeDtypeStruct(
        (8, 4), jnp.float32,
        sharding=NamedSharding(hcg.mesh, P(None, "mp")),
    )
    assert layout.DEFAULT_POLICY.optimizer_state_sharding(v) is None
    sh = layout.PP_SHARDED_STATE.optimizer_state_sharding(v)
    assert sh is not None and tuple(sh.spec) == ("pp", "mp")


# --------------------------------------------- policy-routed mp_layers
def test_tp_layer_specs_route_through_policy(hcg):
    # renaming the policy's mp axis moves every family's spec with it —
    # proof the annotations come FROM the policy, not hard-coded strings
    pol = layout.derive("tp-pp-dp", "mp-on-sep", mp_axis="sep")
    try:
        with layout.use_policy(pol), paddle.LazyGuard():
            col = ColumnParallelLinear(8, 8, gather_output=False)
            row = RowParallelLinear(8, 8, has_bias=False)
            emb = VocabParallelEmbedding(16, 8)
        assert tuple(col.weight.value.sharding.spec) == (None, "sep")
        assert tuple(row.weight.value.sharding.spec) == ("sep", None)
        assert tuple(emb.weight.value.sharding.spec) == ("sep", None)
    finally:
        layout._POLICIES.pop("mp-on-sep", None)
    with paddle.LazyGuard():
        col = ColumnParallelLinear(8, 8, gather_output=False)
    assert tuple(col.weight.value.sharding.spec) == (None, "mp")


class _GoldHead(nn.Layer):
    """Hand-annotated legacy layout: plain layers, weights device_put
    with the historical hard-coded specs."""

    def __init__(self):
        super().__init__()
        self.emb = nn.Embedding(VOCAB, HID)
        self.head = nn.Linear(HID, VOCAB)

    def forward(self, ids):
        return self.head(self.emb(ids))


class _TPHead(nn.Layer):
    def __init__(self):
        super().__init__()
        self.emb = VocabParallelEmbedding(VOCAB, HID)
        self.head = ColumnParallelLinear(HID, VOCAB, gather_output=True)

    def forward(self, ids):
        return self.head(self.emb(ids))


def _legacy_annotate(gold, tp, mesh):
    pairs = [
        (gold.emb.weight, tp.emb.weight, P("mp", None)),
        (gold.head.weight, tp.head.weight, P(None, "mp")),
        (gold.head.bias, tp.head.bias, P("mp")),
    ]
    for g, t, spec in pairs:
        t.value = jax.device_put(
            np.asarray(g.value), NamedSharding(mesh, spec)
        )


def test_layout_policy_equivalence_legacy_vs_default(hcg):
    """Same logits/loss/grads under legacy per-model annotations vs the
    default policy instance (the tentpole's byte-identity pin)."""
    paddle.seed(0)
    gold = _GoldHead()
    tp = _TPHead()
    # the TP net's weights were PLACED by the policy at construction;
    # overwrite with gold's values on the LEGACY hand specs — if the
    # policy had produced different placements, values or grads diverge
    _legacy_annotate(gold, tp, hcg.mesh)
    for (k, a), (_, b) in zip(gold.named_parameters(),
                              tp.named_parameters()):
        assert tuple(a.shape) == tuple(b.shape), k
    rng = np.random.RandomState(1)
    ids = Tensor(jnp.asarray(rng.randint(0, VOCAB, (B, S))))
    labels = Tensor(jnp.asarray(rng.randint(0, VOCAB, (B, S))))

    lg = F.cross_entropy(
        gold(ids).reshape([-1, VOCAB]), labels.reshape([-1])
    )
    lg.backward()
    lt = ParallelCrossEntropy()(
        tp(ids).reshape([-1, VOCAB]), labels.reshape([-1])
    ).mean()
    lt.backward()
    np.testing.assert_allclose(float(lt.numpy()), float(lg.numpy()),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tp.emb.weight.grad.numpy()),
        np.asarray(gold.emb.weight.grad.numpy()),
        rtol=1e-4, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(tp.head.weight.grad.numpy()),
        np.asarray(gold.head.weight.grad.numpy()),
        rtol=1e-4, atol=1e-6,
    )


# --------------------------------------------------- vocab-parallel CE
def _ce_case(dtype, ignore_some):
    rng = np.random.RandomState(7)
    logits = jnp.asarray(rng.randn(B * S, VOCAB), jnp.float32)
    if dtype == "bfloat16":
        logits = logits.astype(jnp.bfloat16)
    labels = np.asarray(rng.randint(0, VOCAB, (B * S,)))
    if ignore_some:
        labels[::5] = -100
    return logits, jnp.asarray(labels)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ignore_some", [False, True])
def test_vocab_ce_parity_vs_unsharded(hcg, dtype, ignore_some):
    """The explicit Megatron CE == unsharded CE, loss AND grad, fp32
    and the AMP O2 storage dtype, with and without ignore_index."""
    logits, labels = _ce_case(dtype, ignore_some)
    with layout.use_policy("pp-sharded-state"):
        lt = Tensor(logits, stop_gradient=False)
        loss = ParallelCrossEntropy()(lt, Tensor(labels))
        loss.mean().backward()
    lr = Tensor(logits, stop_gradient=False)
    ref = F.cross_entropy(lr, Tensor(labels), reduction="none",
                          ignore_index=-100)
    ref.mean().backward()
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(loss.numpy(), np.float32),
        np.asarray(ref.numpy(), np.float32), **tol,
    )
    np.testing.assert_allclose(
        np.asarray(lt.grad.numpy(), np.float32),
        np.asarray(lr.grad.numpy(), np.float32), **tol,
    )


def test_vocab_ce_zero_loss_on_ignored_rows(hcg):
    logits, labels = _ce_case("float32", True)
    with layout.use_policy("pp-sharded-state"):
        per_tok = ParallelCrossEntropy()(Tensor(logits), Tensor(labels))
    got = np.asarray(per_tok.numpy())
    assert (got[np.asarray(labels) == -100] == 0).all()
    assert (got[np.asarray(labels) != -100] > 0).all()


def test_vocab_ce_never_materializes_full_vocab_fp32(hcg):
    """The aval pin: the sharded CE's jaxpr (incl. shard_map bodies,
    whose avals are PER-SHARD) holds zero fp32 arrays of full vocab
    width — its fp32 blocks are [rows, V/mp]. The unsharded fp32
    softmax is the positive control."""
    from tools.lower_7b import _walk_avals, count_fp32_full_vocab_avals

    logits, labels = _ce_case("bfloat16", False)
    jx = jax.make_jaxpr(
        lambda l, y: tp_ops.vocab_parallel_cross_entropy_spmd(l, y)
    )(logits, labels)
    assert count_fp32_full_vocab_avals(jx.jaxpr, VOCAB) == 0
    # ...and the per-shard fp32 block IS there (V/mp wide)
    deg = mesh_mod.axis_size("mp")
    local = [
        a for a in _walk_avals(jx.jaxpr)
        if a.shape and a.shape[-1] == VOCAB // deg
        and np.dtype(a.dtype).name == "float32"
    ]
    assert local, "no per-shard fp32 CE blocks found"
    ref = jax.make_jaxpr(
        lambda l: jax.nn.log_softmax(l.astype(jnp.float32), axis=-1)
    )(logits)
    assert count_fp32_full_vocab_avals(ref.jaxpr, VOCAB) > 0


def test_vocab_ce_grad_matches_in_jit_chain(hcg):
    """value_and_grad through an upstream weight (the compiled-trainer
    AD route) under jit."""
    rng = np.random.RandomState(3)
    w = jnp.asarray(rng.randn(HID, VOCAB), jnp.float32)
    x = jnp.asarray(rng.randn(B * S, HID), jnp.float32)
    y = jnp.asarray(rng.randint(0, VOCAB, (B * S,)))

    def sharded(w):
        return tp_ops.vocab_parallel_cross_entropy_spmd(
            (x @ w).astype(jnp.bfloat16), y
        ).mean()

    def ref(w):
        lg = (x @ w).astype(jnp.bfloat16).astype(jnp.float32)
        lp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(lp, y[:, None], axis=1).mean()

    l1, g1 = jax.jit(jax.value_and_grad(sharded))(w)
    l2, g2 = jax.jit(jax.value_and_grad(ref))(w)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-6)


def test_causal_lm_loss_seam_routes_by_policy(hcg):
    from paddle_tpu.models import causal_lm_loss

    logits, labels = _ce_case("float32", True)
    lt = Tensor(logits.reshape(B, S, VOCAB))
    lb = Tensor(labels.reshape(B, S))
    ref = F.cross_entropy(
        Tensor(logits), Tensor(labels), reduction="none",
        ignore_index=-100,
    )
    # default policy: distributed-softmax route; vocab-parallel policy:
    # explicit shard_map route — both equal the unsharded reference
    for pol in ("tp-pp-dp", "pp-sharded-state"):
        with layout.use_policy(pol):
            got = causal_lm_loss(lt, lb)
        np.testing.assert_allclose(
            np.asarray(got.numpy()), np.asarray(ref.numpy()),
            rtol=1e-5, atol=1e-6, err_msg=pol,
        )


# ------------------------------------------- pp-sharded optimizer state
def _tiny_train(policy, steps=3):
    paddle.seed(11)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.asarray(rng.randint(0, 8, (8,)))
    with layout.use_policy(policy):
        step = CompiledTrainStep(
            net, lambda o, t: F.cross_entropy(o, t), opt
        )
        for _ in range(steps):
            loss, _ = step([Tensor(x)], [Tensor(y)])
    params = {k: np.asarray(p.numpy()) for k, p in
              net.named_parameters()}
    return float(loss.numpy()), params, opt, step


def test_pp_sharded_state_same_numerics_and_sharded_moments(hcg):
    l_def, p_def, _, _ = _tiny_train("tp-pp-dp")
    l_pp, p_pp, opt, step = _tiny_train("pp-sharded-state")
    np.testing.assert_allclose(l_pp, l_def, rtol=1e-5)
    for k in p_def:
        np.testing.assert_allclose(p_pp[k], p_def[k], rtol=1e-5,
                                   atol=1e-7, err_msg=k)
    assert step._layout_policy.name == "pp-sharded-state"
    mats = {
        k: v for k, v in opt._accumulators.items()
        if getattr(v, "ndim", 0) > 1
    }
    assert mats
    for k, v in mats.items():
        assert "pp" in str(v.sharding.spec), (k, v.sharding)


def test_default_policy_leaves_moments_unpinned(hcg):
    _, _, opt, step = _tiny_train("tp-pp-dp")
    assert step._layout_policy.name == "tp-pp-dp"
    for k, v in opt._accumulators.items():
        assert "pp" not in str(
            getattr(getattr(v, "sharding", None), "spec", "")
        )


def test_optimizer_acc_born_on_policy_layout(hcg):
    with paddle.LazyGuard():
        lin = ColumnParallelLinear(8, 8, gather_output=False)
    lin.materialize()
    opt = paddle.optimizer.AdamW(1e-3, parameters=lin.parameters())
    with layout.use_policy("pp-sharded-state"):
        m = opt._acc(lin.weight, "moment1")
    assert tuple(m.sharding.spec) == ("pp", "mp")
    opt2 = paddle.optimizer.AdamW(1e-3, parameters=lin.parameters())
    m2 = opt2._acc(lin.weight, "moment1")  # default policy: mirrors
    assert "pp" not in str(getattr(m2.sharding, "spec", ""))


# ----------------------------------------------------------- lint rule
def test_lint_accepts_policy_axes_on_narrower_mesh():
    from paddle_tpu import analysis
    from paddle_tpu.analysis.jaxpr_lint import LintConfig

    devs = np.array(jax.devices())
    prev_defined = mesh_mod.mesh_defined()
    prev = mesh_mod.get_mesh() if prev_defined else None
    try:
        mesh_mod.set_mesh(Mesh(devs.reshape(-1), ("dp",)))
        n = len(devs)
        other = Mesh(devs.reshape(-1), ("mp",))
        fn = jax.shard_map(
            lambda x: jax.lax.psum(x, "mp"), mesh=other,
            in_specs=P("mp"), out_specs=P(),
        )
        x = jnp.ones((n,), jnp.float32)
        # auto mode + a policy INSTALLED: 'mp' is a policy axis ->
        # clean on the dp-only mesh
        with layout.use_policy("pp-sharded-state"):
            rep = analysis.lint_fn(fn, x, graph="vocab-ce",
                                   config=LintConfig())
        assert not [f for f in rep
                    if f.rule == "collective-mesh-mismatch"]
        # no policy installed: full strictness is kept — the implicit
        # default must not whitelist every standard axis name
        rep0 = analysis.lint_fn(fn, x, graph="vocab-ce",
                                config=LintConfig())
        assert [f for f in rep0
                if f.rule == "collective-mesh-mismatch"]
        # explicit axes are honored verbatim (existing behavior)
        rep2 = analysis.lint_fn(fn, x, graph="vocab-ce",
                                config=LintConfig(mesh_axes=("dp",)))
        assert [f for f in rep2
                if f.rule == "collective-mesh-mismatch"]
        # a truly unknown axis still fires in auto mode
        other2 = Mesh(devs.reshape(-1), ("bogus",))
        fn2 = jax.shard_map(
            lambda x: jax.lax.psum(x, "bogus"), mesh=other2,
            in_specs=P("bogus"), out_specs=P(),
        )
        rep3 = analysis.lint_fn(fn2, x, graph="vocab-ce",
                                config=LintConfig())
        assert [f for f in rep3
                if f.rule == "collective-mesh-mismatch"]
    finally:
        if prev is not None:
            mesh_mod.set_mesh(prev)


# ------------------------------------- compiled pipe + lowering proofs
def test_compiled_pipe_vocab_ce_loss_parity_pp1(hcg):
    """The causal-LM loss path through the compiled pipeline trainer
    (pp degree 1 = the scan branch, which lowers on every jax line):
    vocab-parallel policy numerics == default policy numerics."""
    from types import SimpleNamespace

    from paddle_tpu.distributed.fleet.meta_parallel import (
        PipelineParallel,
    )
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe

    topo = CommunicateTopology(
        ["dp", "pp", "sharding", "sep", "mp"], [4, 1, 1, 1, 2]
    )
    hcg1 = HybridCommunicateGroup(topo)
    cfg = LlamaConfig.tiny(
        vocab_size=32, hidden_size=32, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=2,
    )
    rng = np.random.RandomState(2)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 8)))

    def run(policy):
        paddle.seed(21)
        with layout.use_policy(policy):
            pipe = LlamaForCausalLMPipe(cfg, num_stages=1)
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=pipe.parameters())
            engine = PipelineParallel(
                pipe, hcg1,
                SimpleNamespace(pipeline_configs={
                    "accumulate_steps": 2, "compiled": True,
                }),
            )
            losses = []
            for _ in range(3):
                loss = engine.train_batch((Tensor(ids), Tensor(ids)),
                                          opt)
                losses.append(float(np.asarray(loss.numpy())))
        return losses

    l_def = run("tp-pp-dp")
    l_vp = run("pp-sharded-state")
    np.testing.assert_allclose(l_vp, l_def, rtol=2e-5)
    assert l_def[-1] < l_def[0]  # it actually learns


def test_lower_7b_small_pp_sharded_layout(hcg):
    """The lower_7b flow under the pp-sharded-state policy on a small
    config: moments lower pp-sharded (verified in the module text) and
    zero fp32 full-vocab avals survive in the step jaxpr."""
    import tools.lower_7b as l7
    from paddle_tpu.models import LlamaConfig

    small = LlamaConfig(
        vocab_size=256, hidden_size=32, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        max_position_embeddings=64,
    )
    rep = l7.lower_7b(dp=2, pp=2, mp=2, B=4, S=16, micro_batches=2,
                      cfg=small, min_params=0,
                      layout="pp-sharded-state")
    assert rep["ok"]
    assert rep["layout_policy"] == "pp-sharded-state"
    assert rep["measured_per_chip"]["pp_sharded_state_leaves"] > 0
    assert rep["fp32_full_vocab_avals"] == 0


def test_lower_7b_small_long_context_sep(hcg):
    """S-long small config through the sep ring: the lowering keeps the
    ring collectives and the sep-sharded batch."""
    import tools.lower_7b as l7
    from paddle_tpu.models import LlamaConfig

    small = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        max_position_embeddings=128,
    )
    rep = l7.lower_7b(dp=1, pp=2, mp=2, sep=2, B=4, S=64,
                      micro_batches=2, cfg=small, min_params=0,
                      layout="long-context",
                      budget_geometry=(4, 2, 2, 2, 1, 8192))
    assert rep["ok"] and rep["collective_permute_ops"] > 0
    assert rep["layout_policy"] == "long-context"


def test_measured_per_chip_tables_shrink_by_pp(hcg):
    """Measure-only 7B-flow check on a small config (the real-7B run is
    the layout-smoke gate): pp-sharded-state halves per-chip state."""
    import tools.lower_7b as l7
    from paddle_tpu.models import LlamaConfig

    small = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        max_position_embeddings=64,
    )
    got = {}
    for name in ("tp-pp-dp", "pp-sharded-state"):
        b = l7.build_7b(dp=2, pp=2, mp=2, B=4, S=16, micro_batches=2,
                        cfg=small, min_params=0, layout=name)
        got[name] = l7.measured_per_chip(b["params"], b["opt_state"])
    for row in ("params", "adam_m", "adam_v"):
        d = got["tp-pp-dp"]["rows_gib"][row]
        s = got["pp-sharded-state"]["rows_gib"][row]
        assert s <= d / 2 * 1.05, (row, s, d)
    assert got["pp-sharded-state"]["pp_sharded_state_leaves"] > 0
    assert got["tp-pp-dp"]["pp_sharded_state_leaves"] == 0


def test_per_chip_budget_pp_sharded_hits_roadmap_number():
    """The 18.4 GiB/chip analytic claim at the v5p-64 geometry, and the
    S=8192 long-context budget fitting under it."""
    import tools.lower_7b as l7
    from paddle_tpu.models import LlamaConfig

    cfg = LlamaConfig.llama2_7b()
    n = 6738415616
    b = l7._per_chip_budget(cfg, n, tp=4, pp=2, dp=4, b_micro=1,
                            seq=4096, hbm_gib=95, pp_sharded_state=True)
    assert b["total_gib"] == pytest.approx(29.36, abs=0.05)
    assert b["total_gib_if_pp_sharded_state"] <= 18.4
    assert b["effective_total_gib"] <= 18.4 and b["fits"]
    lc = l7._per_chip_budget(cfg, n, tp=4, pp=2, dp=2, sep=2, b_micro=1,
                             seq=8192, hbm_gib=95, pp_sharded_state=True)
    assert lc["fits"], lc
    assert lc["rows_gib"]["activations_remat"] <= \
        b["rows_gib"]["activations_remat"] * 1.01


def test_bench_long_context_record(hcg):
    """The --long-context impl emits the standard self-describing JSON
    with the layout-policy name echoed (8 devices: the full pp/sep
    geometry, so no ``reduced`` label)."""
    import bench

    rec = bench._long_context_impl(S=32)
    assert rec["layout_policy"] == "long-context"
    assert rec["value"] > 0 and rec["unit"] == "tokens/s"
    assert "geometry" in rec and "window_sec" in rec
    assert "reduced" not in rec
