"""North-star proof: lower the Llama-2-7B Fleet hybrid train step.

BASELINE config #4 is Llama-2-7B under Fleet hybrid TP+PP+DP; the north
star is training it on a v5p-64 (32 chips). Real 7B execution needs that
pod — but PROVING the program is a lowering problem, not an execution
problem: this tool builds the full ``LlamaConfig.llama2_7b`` compiled
hybrid train step (AdamW + AMP O2 bf16 + compiled ppermute pipeline +
Megatron TP + dp batch sharding) over an 8-device mesh with every
parameter ABSTRACT (``paddle.LazyGuard`` — zero weight bytes exist),
lowers it to StableHLO, and asserts:

- the TP collectives (all-reduce family) and the pp ring's
  collective-permute appear in the lowered module;
- every TP weight carries its mp-sharded layout into the lowering;
- the analytic per-chip HBM budget for the v5p-64 geometry
  (tp4 x pp2 x dp4, 95 GB HBM/chip) fits with headroom.

The build runs under a named ``parallel.layout`` policy (``--layout``),
and the report carries MEASURED per-chip bytes computed from the sharded
avals (``sharding.shard_shape`` of every param / Adam-moment leaf), next
to the analytic table — so layout claims are checked, not assumed:

- ``pp-sharded-state``: optimizer moments + fp32 masters additionally
  shard over pp (29.4 -> 18.4 GiB/chip analytic at v5p-64) and the loss
  runs the explicit vocab-parallel CE; the lowered module must carry
  the pp-sharded state layout and the full-step jaxpr must contain ZERO
  fp32 avals of full vocab width (the CE's fp32 blocks are [rows, V/mp]
  shard-local).
- ``long-context``: the S=8192 flagship through the sep ring
  (tp4 x pp2 x sep2 x dp2 at v5p-64), compile-proven under the
  pp-sharded budget.

Run via ``python bench.py --lower-7b`` or ``make layout-smoke`` (both
self-provision a virtual 8-device CPU mesh) or from
``__graft_entry__.dryrun_multichip`` phase 4.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GiB = 1024 ** 3


def _sdy_dims(spec, ndim):
    """A PartitionSpec's per-dimension axes as Shardy writes them into a
    lowered module: ``P("pp", "mp")`` -> ``[{"pp"}, {"mp"}]>`` (the
    closing ``>`` ends the ``#sdy.sharding<@mesh, ...>`` attribute, so
    a shorter spec cannot match a longer one's prefix)."""
    dims = []
    for i in range(ndim):
        axes = spec[i] if i < len(spec) else None
        if axes is None:
            axes = ()
        elif isinstance(axes, str):
            axes = (axes,)
        dims.append("{" + ", ".join(f'"{a}"' for a in axes) + "}")
    return "[" + ", ".join(dims) + "]>"


def _per_chip_budget(cfg, n_params, tp, pp, dp, b_micro, seq, hbm_gib,
                     sep=1, pp_sharded_state=False):
    """Analytic steady-state per-chip HBM for the hybrid layout.

    Parameters + Adam state are mp-sharded (and pp-replicated in the
    default layout — each rank holds all blocks, computes only its pp
    slice; ``pp_sharded_state`` shards masters+moments+compute copy over
    pp too, the policy lever — the table reports both totals either
    way). Activations: block-boundary remat stores only each block's
    input per in-flight microbatch, divided over sep when the sequence
    is context-parallel; flash/ring attention never materializes S^2;
    the loss block is the vocab-sharded [rows, V/tp] fp32 shard. All in
    bytes per chip.
    """
    L, H, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
    rows = {
        "params_master_fp32": 4 * n_params / tp,
        "adam_m_fp32": 4 * n_params / tp,
        "adam_v_fp32": 4 * n_params / tp,
        "params_bf16_compute_copy": 2 * n_params / tp,
        "grads_fp32_transient": 4 * n_params / tp,
        "activations_remat": pp * (L / pp) * b_micro * seq * H * 2 / sep,
        "logits_fp32_microbatch": b_micro * seq * (V / tp) * 4 / sep,
        "rope_cache_bf16": seq * (H // cfg.num_attention_heads) * 2 * 2,
    }
    total = sum(rows.values())
    # the pp-sharded-state lever: masters + moments + bf16 compute copy
    # (14 bytes/param) keep only their own stage's slice per rank
    total_pp_sharded = total - (14 * n_params / tp) * (1 - 1 / pp)
    effective = total_pp_sharded if pp_sharded_state else total
    geom = f"tp{tp} x pp{pp}" + (f" x sep{sep}" if sep > 1 else "") + \
        f" x dp{dp}"
    return {
        "geometry": f"v5p-64: {geom} ({tp * pp * sep * dp} chips, "
                    f"{hbm_gib} GiB HBM each)",
        "b_micro": b_micro, "seq": seq,
        "rows_gib": {k: round(v / GiB, 2) for k, v in rows.items()},
        "total_gib": round(total / GiB, 2),
        "total_gib_if_pp_sharded_state": round(total_pp_sharded / GiB, 2),
        "pp_sharded_state": pp_sharded_state,
        "effective_total_gib": round(effective / GiB, 2),
        "hbm_gib": hbm_gib,
        "fits": effective < hbm_gib * GiB,
        "headroom_gib": round((hbm_gib * GiB - effective) / GiB, 2),
    }


def _leaf_per_chip_bytes(sds):
    """Per-chip bytes of one (possibly sharded) abstract leaf, measured
    from its sharding's shard_shape — the lowered module's layout, not
    an assumption."""
    import numpy as np

    shape = tuple(sds.shape)
    sh = getattr(sds, "sharding", None)
    local = sh.shard_shape(shape) if hasattr(sh, "shard_shape") else shape
    return int(np.prod(local, dtype=np.int64)) * np.dtype(sds.dtype).itemsize


def measured_per_chip(params, opt_state, pp_axis="pp"):
    """MEASURED per-chip bytes of params + Adam moments on the build
    mesh, summed from every leaf's sharded aval, plus how many state
    leaves actually carry the pp axis."""
    rows = {
        "params": sum(_leaf_per_chip_bytes(v) for v in params.values()),
        "adam_m": sum(
            _leaf_per_chip_bytes(a[0]) for a in opt_state.values()
        ),
        "adam_v": sum(
            _leaf_per_chip_bytes(a[1]) for a in opt_state.values()
        ),
    }
    pp_leaves = sum(
        1
        for accs in opt_state.values()
        for a in accs
        if pp_axis in str(getattr(getattr(a, "sharding", None), "spec", ""))
    )
    return {
        "rows_gib": {k: round(v / GiB, 4) for k, v in rows.items()},
        "total_gib": round(sum(rows.values()) / GiB, 4),
        "pp_sharded_state_leaves": pp_leaves,
        "note": "per-chip bytes from sharding.shard_shape on the "
                "BUILD mesh (abstract avals — zero real bytes exist)",
    }


def memory_cross_check(built, budget, tolerance=0.10):
    """Cross-check the analytic v5p-64 table against the memory_lint
    per-chip aval math: ``analysis.per_chip_bytes`` (the SAME
    ``sharding.shard_shape`` accounting the serving/train footprint
    estimators use) re-derives the per-chip state bytes of the built
    7B from its sharded avals. For the pp-sharded-state layout the
    state figure must land within ``tolerance`` of the analytic
    effective total — the 18.4 GiB/chip north-star pin checked from
    two independent directions (closed-form formula vs per-leaf
    sharded-aval sum)."""
    from paddle_tpu import analysis

    params, opt_state = built["params"], built["opt_state"]
    rows = {
        "params": sum(
            analysis.per_chip_bytes(v) for v in params.values()
        ),
        "adam_m": sum(
            analysis.per_chip_bytes(a[0]) for a in opt_state.values()
        ),
        "adam_v": sum(
            analysis.per_chip_bytes(a[1]) for a in opt_state.values()
        ),
    }
    total = sum(rows.values())
    analytic = budget["effective_total_gib"] * GiB
    if not analytic:
        # a cut-down config (the tier-1 harness tests): the analytic
        # table is kept in hundredths of a GiB and rounds to zero, so
        # there is nothing to hold the aval sum against
        return {
            "state_per_chip_gib": round(total / GiB, 4),
            "analytic_effective_gib": 0.0,
            "skipped": "analytic table rounds to 0 GiB at this size",
        }
    out = {
        "rows_gib": {k: round(v / GiB, 4) for k, v in rows.items()},
        "state_per_chip_gib": round(total / GiB, 4),
        "analytic_effective_gib": budget["effective_total_gib"],
        "ratio_vs_analytic": round(total / analytic, 4),
        "pp_sharded_state": budget["pp_sharded_state"],
        "tolerance": tolerance,
        "note": "per-chip state bytes re-derived through "
                "analysis.per_chip_bytes (memory_lint's shard_shape "
                "accounting) on the BUILD mesh",
    }
    if budget["pp_sharded_state"]:
        within = abs(total - analytic) <= tolerance * analytic
        out["within_tolerance"] = within
        assert within, (
            f"memory_lint per-chip state {total / GiB:.2f} GiB vs "
            f"analytic {analytic / GiB:.2f} GiB: outside "
            f"±{tolerance:.0%}"
        )
    return out


def build_7b(dp=2, pp=2, mp=2, sep=1, B=8, S=4096, micro_batches=4,
             cfg=None, min_params=6.5e9, layout="tp-pp-dp"):
    """Build the abstract 7B hybrid trainer under a layout policy on the
    current (>= dp*pp*sep*mp device) mesh. Returns the build dict used
    by :func:`lower_7b` and the measure-only layout-smoke path."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology,
        HybridCommunicateGroup,
    )
    from paddle_tpu.jit.pipeline_trainer import CompiledPipelineTrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe
    from paddle_tpu.parallel import layout as layout_mod

    pol = layout_mod.resolve(layout)
    topo = CommunicateTopology(
        ["dp", "pp", "sharding", "sep", "mp"], [dp, pp, 1, sep, mp]
    )
    hcg = HybridCommunicateGroup(topo)
    mesh = hcg.mesh

    if cfg is None:
        cfg = LlamaConfig.llama2_7b(max_position_embeddings=max(S, 4096))
    prev = layout_mod.set_policy(pol)
    try:
        with paddle.LazyGuard():
            # recompute_interval=1: block-boundary remat — the activation
            # row of the budget table assumes it
            net = LlamaForCausalLMPipe(cfg, num_stages=pp,
                                       recompute_interval=1)
        n_params = net.num_params()  # works abstractly: SDS has .shape
        assert n_params > min_params, (
            f"model has only {n_params} params (expected > {min_params:g})"
        )

        opt = paddle.optimizer.AdamW(3e-4, parameters=net.parameters())
        trainer = CompiledPipelineTrainStep(
            net, lambda out, *lbls: net._loss_fn(out, *lbls), opt,
            micro_batches=micro_batches, num_virtual=1,
            amp_level="O2", amp_dtype="bfloat16",
        )

        params = {k: p.value for k, p in net.named_parameters()}
        # steady-state placements: the trainer's in-step policy
        # constraints keep masters on the master-param layout after the
        # first step, so the lowering's input avals carry it too
        if pol.pp_shard_master_params:
            params = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=pol.master_param_sharding(v) or v.sharding,
                )
                for k, v in params.items()
            }
        # abstract AdamW state mirroring _gather_opt_state's layout; the
        # policy's optimizer-state rule decides where each moment lives
        # (param's own placement by default, +pp under pp-sharded-state)
        opt_state = {}
        for k, v in params.items():
            sh = pol.optimizer_state_sharding(v) or v.sharding
            opt_state[k] = (
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh),
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh),
            )
    finally:
        layout_mod.set_policy(prev)
    return {
        "cfg": cfg, "net": net, "trainer": trainer, "mesh": mesh,
        "policy": pol, "params": params, "opt_state": opt_state,
        "n_params": n_params, "B": B, "S": S,
        "micro_batches": micro_batches,
        "geometry": {"dp": dp, "pp": pp, "sep": sep, "mp": mp},
    }


def _walk_avals(jaxpr):
    """Yield every output aval in a jaxpr incl. sub-jaxprs (shard_map
    bodies carry PER-SHARD shapes — that is the point of the pin).
    Traversal is the analysis linter's maintained walker."""
    from paddle_tpu.analysis.jaxpr_lint import _walk_eqns

    for eqn, _ in _walk_eqns(jaxpr):
        for ov in eqn.outvars:
            a = getattr(ov, "aval", None)
            if a is not None and getattr(a, "shape", None) is not None:
                yield a


def fp32_full_vocab_avals(jaxpr, vocab_size, min_rows=1):
    """Shapes of fp32 avals whose trailing dim is the FULL vocab and
    whose leading dims hold >= ``min_rows`` rows — the activation block
    the vocab-parallel CE must never materialize (per-shard avals inside
    its shard_map are [rows, V/mp], so a policy-routed step yields
    none). ``min_rows`` separates the [B*S, V] logits/softmax block
    from fp32 WEIGHT-shaped avals ([hidden, V] masters/grads/moments,
    which the mp axis shards and this pin is not about) — callers with
    params in the graph pass the flattened batch token count."""
    import numpy as np

    return [
        tuple(a.shape)
        for a in _walk_avals(jaxpr)
        if a.shape
        and int(a.shape[-1]) == int(vocab_size)
        and np.dtype(a.dtype).name == "float32"
        and int(np.prod(a.shape[:-1], dtype=np.int64)) >= min_rows
    ]


def count_fp32_full_vocab_avals(jaxpr, vocab_size, min_rows=1):
    return len(fp32_full_vocab_avals(jaxpr, vocab_size, min_rows))


def lower_7b(dp=2, pp=2, mp=2, sep=1, B=8, S=4096, micro_batches=4,
             write_notes=False, cfg=None, min_params=6.5e9,
             layout="tp-pp-dp", budget_geometry=None, check_avals=None):
    """Build + lower the 7B hybrid step on the current mesh under a
    layout policy. Returns the report dict; raises if any assertion
    fails. ``cfg``/``min_params`` exist for the CI-sized version of this
    flow (tests run the identical path on a small config).
    ``budget_geometry``: (tp, pp, dp, sep, b_micro, seq) override for
    the analytic v5p-64 table. ``check_avals`` defaults to the policy's
    vocab_parallel_loss flag (walking the full-step jaxpr costs one
    extra abstract trace)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core import random as random_mod
    from paddle_tpu.parallel import layout as layout_mod

    built = build_7b(dp=dp, pp=pp, mp=mp, sep=sep, B=B, S=S,
                     micro_batches=micro_batches, cfg=cfg,
                     min_params=min_params, layout=layout)
    cfg = built["cfg"]
    pol = built["policy"]
    mesh = built["mesh"]
    trainer = built["trainer"]
    params, opt_state = built["params"], built["opt_state"]
    n_params = built["n_params"]

    buffers = {}
    in_spec = pol.batch_spec(2)
    ids = jax.ShapeDtypeStruct(
        (B, S), jnp.int32, sharding=NamedSharding(mesh, in_spec)
    )
    lbls = jax.ShapeDtypeStruct(
        (B, S), jnp.int32, sharding=NamedSharding(mesh, in_spec)
    )
    prev = layout_mod.set_policy(pol)
    try:
        trainer._build()
        step_args = (
            params, opt_state, buffers, jnp.float32(3e-4),
            jnp.float32(1), random_mod.next_key(), (ids,), (lbls,),
        )
        lowered = jax.jit(
            trainer._step, donate_argnums=(0, 1, 2)
        ).lower(*step_args)
        txt = lowered.as_text()

        if check_avals is None:
            check_avals = pol.vocab_parallel_loss
        n_full_vocab_fp32 = None
        if check_avals:
            # min_rows = the flattened batch token count: the loss runs
            # whole-batch in the pipe suffix, so the forbidden block is
            # [B*S, V]; fp32 [hidden, V] weight avals stay out of scope
            assert B * S > cfg.hidden_size, (
                "aval pin needs B*S > hidden to tell the logits block "
                "from weight-shaped fp32 avals"
            )
            closed = jax.make_jaxpr(trainer._step)(*step_args)
            offending = fp32_full_vocab_avals(
                closed.jaxpr, cfg.vocab_size, min_rows=B * S
            )
            n_full_vocab_fp32 = len(offending)
            assert not (pol.vocab_parallel_loss and offending), (
                f"vocab-parallel CE still materializes fp32 full-vocab "
                f"activation blocks: {offending[:4]}"
            )
    finally:
        layout_mod.set_policy(prev)

    # --- assertions on the lowered module -----------------------------
    n_cperm = txt.count("collective_permute") + txt.count(
        "collective-permute"
    )
    n_ar = txt.count("all_reduce") + txt.count("all-reduce")
    assert n_cperm > 0, "no collective-permute: pp ring missing"
    assert n_ar > 0, "no all-reduce: TP/DP reductions missing"
    tp_sharded = [
        k for k, v in params.items()
        if v.sharding is not None
        and pol.mp_axis in str(getattr(v.sharding, "spec", ""))
    ]
    # every decoder block contributes 7 TP weights (q,k,v,o,gate,up,down)
    expect_tp = 7 * cfg.num_hidden_layers + 2  # + embedding + lm head
    assert len(tp_sharded) >= expect_tp, (
        f"only {len(tp_sharded)} mp-sharded params, expected "
        f">= {expect_tp}"
    )
    assert "bf16" in txt, "no bf16 in lowered module (AMP O2 missing)"

    measured = measured_per_chip(params, opt_state, pp_axis=pol.pp_axis)
    if pol.pp_shard_optimizer_state:
        # the pp-sharded layout must be IN the lowered module, not just
        # the input avals: every distinct moment sharding the policy
        # produced must appear as a Shardy sharding annotation
        pinned = {
            _sdy_dims(a.sharding.spec, len(a.shape))
            for accs in opt_state.values()
            for a in accs
            if pol.pp_axis in str(getattr(a.sharding, "spec", ""))
        }
        assert pinned, "pp-sharded-state policy produced no pinned moments"
        missing = [h for h in pinned if h not in txt]
        assert not missing, (
            f"pp-sharded moment layouts absent from the lowered module: "
            f"{missing[:3]}"
        )
        assert measured["pp_sharded_state_leaves"] > 0
    if budget_geometry is None:
        budget_geometry = (4, 2, 4, 1, 1, S)
    g_tp, g_pp, g_dp, g_sep, g_bm, g_seq = budget_geometry
    budget = _per_chip_budget(
        cfg, n_params, tp=g_tp, pp=g_pp, dp=g_dp, sep=g_sep,
        b_micro=g_bm, seq=g_seq, hbm_gib=95,
        pp_sharded_state=pol.pp_shard_optimizer_state,
    )
    assert budget["fits"], f"7B does not fit v5p-64: {budget}"
    mem_cross = memory_cross_check(built, budget)

    report = {
        "ok": True,
        "model": "llama2_7b", "n_params": n_params,
        "mesh": built["geometry"],
        "layout_policy": pol.name,
        "layout": pol.describe(),
        "batch": {"B": B, "S": S, "micro_batches": micro_batches,
                  "amp": "O2-bf16"},
        "lowered_bytes": len(txt),
        "collective_permute_ops": n_cperm,
        "all_reduce_ops": n_ar,
        "mp_sharded_params": len(tp_sharded),
        "fp32_full_vocab_avals": n_full_vocab_fp32,
        "measured_per_chip": measured,
        "memory_cross_check": mem_cross,
        "v5p64_budget": budget,
    }
    print("lower_7b: " + json.dumps(report))
    if write_notes:
        write_report(report)
    return report


def write_report(report):
    """Merge a layout's report into LOWER_7B.json: the default layout
    keeps the historical top-level shape, every layout lands under
    ``layouts[policy_name]`` so the file carries per-chip totals for
    all proven layouts side by side."""
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "LOWER_7B.json",
    )
    existing = {}
    try:
        with open(out) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        pass
    layouts = dict(existing.get("layouts", {}))
    name = report.get("layout_policy", "tp-pp-dp")
    layouts[name] = {k: v for k, v in report.items() if k != "layouts"}
    top = (
        layouts.get("tp-pp-dp")
        or {k: v for k, v in existing.items() if k != "layouts"}
        or layouts[name]
    )
    merged = dict(top)
    merged["layouts"] = layouts
    with open(out, "w") as f:
        json.dump(merged, f, indent=1)


if __name__ == "__main__":
    layout = "tp-pp-dp"
    for i, a in enumerate(sys.argv):
        if a == "--layout" and i + 1 < len(sys.argv):
            layout = sys.argv[i + 1]
    if layout == "long-context":
        lower_7b(dp=1, pp=2, mp=2, sep=2, B=4, S=8192, write_notes=True,
                 layout=layout, budget_geometry=(4, 2, 2, 2, 1, 8192))
    else:
        lower_7b(write_notes=True, layout=layout)
