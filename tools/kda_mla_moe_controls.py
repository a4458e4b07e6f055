#!/usr/bin/env python3
"""What the limits of ``benchmarks/reference/kda_mla_moe_decoder.py``
are set against, run again: every fault and every lower precision the
file quotes a reading for, through the functions the cell's job calls.

    python3 tools/kda_mla_moe_controls.py --seeds <n> [<n> ...] [--out <file>]

on the chip; ``--toy`` rehearses the control flow on the CPU at a toy
size (no number from there is a reading). Builds
``kimi-linear-48b-a3b-ep2-d5`` as the benchmark does and, a seed:

A. **The served tokens** (``benchmarks/jobs/serve._check``, the cell's
   ``check``: seeded requests through the front end of a
   ``PagedServingEngine`` built as the cell builds it, every served
   token against the reference), once as served and once under each
   FAULT, planted in what the engine hands its own adoption program
   (``_run(("adopt", bucket), ...)``), a fresh engine each:

   - ``rows_next``: state and tail adopted into the neighbouring row
     (the decode row keeps what it held: zeros);
   - ``latent_elsewhere``: the latent block scattered through the
     neighbouring row's page table (a free row's: the garbage page);
   - ``tail_off_by_one``: the convolution tail as it stood one token
     before the prompt's end;
   - ``pages_eighth_lost``: every eighth page of the latent lost.

   ``SERVE_LOGIT_GAP`` and ``SERVE_MEAN_GAP`` must hold the first and
   refuse the faults the file says they refuse (with random weights a
   softmax over 16 k tokens averages to nearly nothing, so the served
   tokens do not see the latent: B's read-back does).

B. **The path check** (``serve_kda_mla_moe.reference_side`` once, of
   the weights as the seed made them, then ``served_side`` and
   ``judge``), once as stated, once under each of A's faults planted
   in the admission that ``adopted_side`` reads back (the engine's own
   prefill, page claim and adoption program; the other readings as
   stated), and once under each LOWER PRECISION or fault planted in the
   program:

   - ``bf16_state``: a KDA row's state kept in bfloat16;
   - ``bf16_router``: the router's logits and scores each rounded to
     bfloat16;
   - ``bf16_contraction``: the absorbed step's two latent contractions'
     results and the softmax between them (scaled scores, exponentials,
     their sum, the quotient) each rounded to bfloat16, where the
     program states float32 (the chip's matrix unit accumulates in
     float32 whatever the result's type: this is what a program that
     does not state it comes to there);
   - ``fp8_latent``: the latent through float8 e4m3 as it is written;
   - ``unfrozen_scan``: the chunked scan not frozen past ``length``;
   - ``fp8_experts``: the routed experts' weights through float8 e4m3,
     one scale an output channel (last: they are not restored).

   Each must come out ``ok=false`` through ``judge``, by the limits the
   reference's file names for it.

Roundings are ``lax.reduce_precision`` or arithmetic, which the
compiler may not fold away (a bf16 convert pair it may). One JSON line
a reading; the last line, ``verdict``, is true where every sound run
passed and every planted one failed as expected. Not a benchmark:
nothing here is timed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "kimi-linear-serve-longdoc-batch32"
# the limits each planted run must break, by the reading's name on the
# judged line (``judge``) or on job serve's ``check`` line
SERVED_FAULTS = {
    "rows_next": {"max_logit_gap", "mean_logit_gap"},
    "tail_off_by_one": {"max_logit_gap", "mean_logit_gap"},
    # recorded, not refused: the served tokens do not see the latent
    "latent_elsewhere": set(),
    "pages_eighth_lost": set(),
}
# ... and on the judged line, where the engine's own admission is read
# back (``adopted_*``)
ADOPTION_FAULTS = {
    "rows_next": {"adopted_state_err_median", "adopted_tail_err_median"},
    "latent_elsewhere": {"adopted_latent_err_median",
                         "adopted_latent_err_p90"},
    "tail_off_by_one": {"adopted_tail_err_median"},
    "pages_eighth_lost": {"adopted_latent_err_p90"},
}
PATH_CONTROLS = {
    "bf16_state": {"kernel_state_err_max"},
    "bf16_router": {"route_elsewhere"},
    "bf16_contraction": {"mla_step_err_p90"},
    "fp8_latent": {"mla_step_err_p90"},
    "unfrozen_scan": {"path_err_median", "path_err_p90",
                      "stepped_state_err_median"},
    "fp8_experts": {"ffn_err_p90"},
}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", choices=["served", "path", "adoption"],
                    default=None,
                    help="A alone, B alone, or B's adoption faults alone")
    ap.add_argument("--toy", action="store_true",
                    help="a toy size on the CPU: rehearses the control flow")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from benchmarks import harness
    from benchmarks.jobs import serve, serve_kda_mla_moe as job
    from benchmarks.models import kda_mla_moe_decoder as builder
    from benchmarks.reference import kda_mla_moe_decoder as ref
    from paddle_tpu.models import kimi_linear, solar_open2, xing4
    from paddle_tpu.serving import PagedServingEngine, ServingFrontend

    paddle.jit.place_compile_cache()
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    cfg = _json("benchmarks", "configs", f"{cell['config']}.json")
    if args.toy:
        from benchmarks.tests.test_kda_mla_moe import TOY

        cfg = dict(TOY)
        cell = dict(
            cell, param_dtype="float32", engine={
                "max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
                "min_bucket": 16, "cache_dtype": "bfloat16"},
            check={"prompt_lens": [24, 32], "max_new": 4, "pad_to": 48},
            path_check={"tokens": 32, "steps": 3, "stride": 2,
                        "ffn_rows": 16})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        open(args.out, "w").close()

    def say(**fields):
        print(json.dumps(fields), flush=True)
        if args.out:        # line by line: a run that dies keeps its lines
            with open(args.out, "a") as f:
                f.write(json.dumps(fields) + "\n")

    # the lines the job's own functions print, kept for the verdict
    printed, line = {}, harness.line

    def keeping(kind, **fields):
        printed[kind] = fields
        line(kind, **fields)

    harness.line = keeping
    bf16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7)

    def through_fp8(f, scale):
        # e4m3 by arithmetic (four significant bits, least exponent -6,
        # largest value 448)
        a = jnp.abs(f / scale)
        step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3)
        return jnp.sign(f) * jnp.round(a / step) * step * scale

    def fp8_of(a, axis):
        f = a.astype(jnp.float32)
        return through_fp8(
            f, jnp.maximum(jnp.max(jnp.abs(f), axis, keepdims=True), 1e-30)
            / 448.0).astype(a.dtype)

    # --------------------------------------------- A. the served tokens
    def adoption_fault(name):
        """What an engine hands its adoption program under the fault
        ``name``: ``(arena, block, page ids, row)`` rewritten, the
        first token and the tokens it is merged into (``feed``) as
        they came."""
        def plant(engine, arena, block, page_ids, row, *feed):
            rows = engine.max_batch_size
            if name == "rows_next":
                row = (row + 1) % rows
            elif name == "latent_elsewhere":
                there = engine._tables[(int(row) + 1) % rows]
                page_ids = jnp.where(page_ids > 0, jnp.asarray(
                    there[:page_ids.shape[0]], page_ids.dtype), 0)
            elif name == "pages_eighth_lost":
                page_ids = jnp.where(
                    jnp.arange(page_ids.shape[0]) % 8 == 0, 0, page_ids)
            elif name == "tail_off_by_one":
                # a row's arrays are its state [1, H, d, d], then its
                # tail [1, K - 1, channels]
                block = [jnp.pad(a[:, :-1], ((0, 0), (1, 0), (0, 0)))
                         if kept and a.ndim == 3 else a
                         for a, kept in zip(block, engine._row_arrays)]
            return (arena, block, page_ids, row, *feed)

        return plant

    def planted_engine(engine, name):
        plant, run = adoption_fault(name), engine._run
        engine._run = lambda key, fn, *a: run(key, fn, *(
            plant(engine, *a) if key[0] == "adopt" else a))

    def served_check(ctx, net, fault):
        engine = PagedServingEngine(net, **cell["engine"])
        if fault is not None:
            planted_engine(engine, fault)
        fe = ServingFrontend(engine).start()
        try:
            ok = serve._check(ctx, net, cfg, fe.port, cell["check"])
        finally:
            fe.stop(close_engine=True)
        got = printed.get("check", {})
        broke = {k for k, limit in (("max_logit_gap", "allowed"),
                                    ("mean_logit_gap", "allowed_mean"))
                 if k in got and not got[k] <= got[limit]}
        say(seed=ctx.seed, served=fault or "as_served", ok=bool(ok),
            max_logit_gap=got.get("max_logit_gap"),
            mean_logit_gap=got.get("mean_logit_gap"), broke=sorted(broke))
        del engine, fe
        gc.collect()
        return ok, broke

    # ------------------------------------------------ B. the path check
    @contextlib.contextmanager
    def planted(name, net):
        """The program under the lower precision or fault ``name``."""
        undo = []

        def patch(owner, attr, value):
            undo.append((owner, attr, getattr(owner, attr)))
            setattr(owner, attr, value)

        if name == "bf16_state":
            stated = kimi_linear.KimiLinearConfig.row_layout
            patch(kimi_linear.KimiLinearConfig, "row_layout",
                  lambda self: [tuple(
                      (shape, "bfloat16" if len(shape) == 3 else dtype)
                      for shape, dtype in layer) for layer in stated(self)])
        elif name == "bf16_router":
            patch(solar_open2, "moe_scores",
                  lambda h, w: bf16(jax.nn.sigmoid(bf16(jnp.dot(
                      h.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)))))
        elif name == "bf16_contraction":
            def absorbed(q_nope, q_rope, view, w_kvb, mask, scale):
                h, dn = q_nope.shape[2], q_nope.shape[3]
                kvl = w_kvb.shape[0]
                w = w_kvb.reshape(kvl, h, -1)
                q_abs = jnp.einsum("bqhd,chd->bqhc", q_nope, w[..., :dn])
                q_cat = jnp.concatenate(
                    [q_abs, q_rope.astype(q_abs.dtype)], -1)
                q_cat = jnp.pad(q_cat, ((0, 0),) * 3 + (
                    (0, view.shape[-1] - q_cat.shape[-1]),))
                view = view.astype(q_cat.dtype)
                s = bf16(jnp.einsum("bqhc,bkc->bhqk", q_cat, view,
                                    preferred_element_type=jnp.float32))
                s = bf16(s * scale)
                if mask is not None:
                    s = bf16(s + mask)
                e = bf16(jnp.exp(bf16(s - jnp.max(s, -1, keepdims=True))))
                p = bf16(e / bf16(jnp.sum(e, -1, keepdims=True)))
                o_lat = bf16(jnp.einsum(
                    "bhqk,bkc->bqhc", p.astype(view.dtype), view,
                    preferred_element_type=jnp.float32))[..., :kvl]
                return jnp.einsum("bqhc,chd->bqhd",
                                  o_lat.astype(view.dtype), w[..., dn:])

            patch(xing4, "mla_absorbed", absorbed)
        elif name == "fp8_latent":
            core = xing4.mla_core
            patch(xing4, "mla_core",
                  lambda q, ckv, k_rope, *a, **kw: core(
                      q, fp8_of(ckv, -1), fp8_of(k_rope, -1), *a, **kw))
        elif name == "unfrozen_scan":
            scan = solar_open2.kda_scan
            patch(solar_open2, "kda_scan",
                  lambda q, k, v, g, beta, state, chunk, length=None: scan(
                      q, k, v, g, beta, state, chunk))
        elif name == "fp8_experts":
            # an expert at a time: no float32 copy of a layer's stack
            to_fp8 = jax.jit(lambda a: jax.lax.map(
                lambda w: fp8_of(w, 0), a), donate_argnums=0)
            for key, p in net.named_parameters():
                if ".mlp.experts_" in key:
                    p.value = to_fp8(p.value)
        try:
            yield
        finally:
            for owner, attr, value in reversed(undo):
                setattr(owner, attr, value)

    def path_check(ctx, net, side, name, stated=None):
        """``judge`` of the served side under ``name``: a control of
        the program (every reading taken again) or, given the readings
        ``stated``, a fault of the adoption (the engine's admission
        taken again, the other readings as stated)."""
        if stated is not None:
            ok = job.judge(ctx, {**stated, **job.adopted_side(
                ctx, net, side, adoption_fault(name))})
        else:
            with planted(name, net) if name else contextlib.nullcontext():
                stated = job.served_side(ctx, net, side)
                ok = job.judge(ctx, stated)
        got = printed.get("check_path", {})
        broke = {k[len("allowed_"):] for k, limit in got.items()
                 if k.startswith("allowed_")
                 and not got[k[len("allowed_"):]] <= limit}
        say(seed=ctx.seed, path=name or "as_stated", ok=bool(ok),
            broke=sorted(broke),
            **{k: got[k] for k in got if f"allowed_{k}" in got})
        gc.collect()
        return ok, broke, stated

    verdict = True
    for seed in args.seeds:
        ctx = types.SimpleNamespace(seed=seed, config=cfg, cell=cell,
                                    builder=builder, reference=ref)
        net, _ = builder.build(cfg, seed, cell.get("param_dtype", "bfloat16"))
        net.eval()
        if args.only in (None, "served"):
            ok, _ = served_check(ctx, net, None)
            verdict = verdict and ok
            for fault, must in SERVED_FAULTS.items():
                ok, broke = served_check(ctx, net, fault)
                if must and (ok or not must <= broke):
                    verdict = False
        if args.only != "served":
            side = job.reference_side(ctx, builder.weights(net))
            gc.collect()
            ok, _, stated = path_check(ctx, net, side, None)
            verdict = verdict and ok
            for fault, must in ADOPTION_FAULTS.items():
                ok, broke, _ = path_check(ctx, net, side, fault, stated)
                if ok or not must <= broke:
                    verdict = False
            del stated
            for control, must in PATH_CONTROLS.items():
                if args.only == "adoption":
                    break
                ok, broke, _ = path_check(ctx, net, side, control)
                if ok or not must & broke:
                    verdict = False
            del side
        del net
        gc.collect()
    say(verdict=bool(verdict), seeds=args.seeds)
    return 0 if verdict or args.toy else 1


if __name__ == "__main__":
    sys.exit(main())
