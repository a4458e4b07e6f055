#!/usr/bin/env python3
"""What the new cell's sharper correctness limits can tell apart, on
the chip, and how often routing parts from the reference.

    python3 tools/latent_moe_precision.py --seed <n> [--out <file>]

Builds ``xing4.0-29b-a4b-d6`` as the benchmark does and runs the cell's
own comparison (``benchmarks/jobs/serve_latent_moe``: ``reference_side``
once, from the weights as the seed made them, then ``served_side`` and
``judge``, the functions ``run.py``'s job calls) once as the
configuration states it and once under each LOWER precision:

- ``bf16``: the program as it is (what every limit must pass);
- ``router_bf16``: the router's logits and scores each rounded to
  bfloat16 (what a router that keeps them in bf16 chooses from);
- ``experts_int8``: the routed experts' weights rounded to int8, one
  scale an output channel (kept in bf16 storage: the rounding is what
  is tested, not a kernel);
- ``experts_fp8``: the same through float8 e4m3 (by arithmetic);
- ``cache_fp8``: the prefilled latent pages rounded through float8
  e4m3 as they are adopted.

One JSON line a variant with the four readings, their limits and
``passes``. Under ``bf16`` the served prefill also records the experts
each token chose in each layer; the line ``routing`` gives the share of
tokens whose set differs from the reference's, a layer, over all
positions and over the decisive ones the comparison uses. The line
``garbage`` gives what ``served_token_gaps`` reads for tokens drawn at
random (the upper reading of the served-token limits). Not a
benchmark: nothing here is timed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "xing4-29b-serve-longctx-batch"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--toy", action="store_true",
                    help="a toy size on the CPU: rehearses the control flow")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from benchmarks.jobs import serve_latent_moe as job
    from benchmarks.models import latent_moe_decoder as builder
    from benchmarks.reference import latent_moe_decoder as ref
    from paddle_tpu.models import xing4
    from paddle_tpu.quantization import kv

    paddle.jit.place_compile_cache()
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    cfg = _json("benchmarks", "configs", f"{cell['config']}.json")
    if args.toy:
        from benchmarks.tests.test_latent_moe import TOY

        cfg = dict(TOY)
        cell = dict(
            cell, param_dtype="bfloat16", engine={
                "max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
                "min_bucket": 16, "cache_dtype": "bfloat16"},
            check={"prompt_lens": [24, 32], "max_new": 4, "pad_to": 48},
            path_check={"tokens": 32, "from": 4, "steps": 2,
                        "prefill_rows": 2, "ffn_rows": 16})
    dtype = cell["param_dtype"]
    net, pcfg = builder.build(cfg, args.seed, dtype)
    net.eval()
    ctx = types.SimpleNamespace(seed=args.seed, config=cfg, cell=cell,
                                builder=builder, reference=ref)
    params = dict(net.named_parameters())
    experts = [k for k in params if ".mlp.experts_" in k]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        open(args.out, "w").close()

    def say(**fields):
        print(json.dumps(fields), flush=True)
        if args.out:        # line by line: a run that dies keeps its lines
            with open(args.out, "a") as f:
                f.write(json.dumps(fields) + "\n")

    # the reference, ONCE, of the weights as the seed made them
    side = job.reference_side(ctx, builder.weights(net))
    n = len(side["decode_rows"])
    rng = np.random.default_rng(args.seed + 3)
    drawn = rng.integers(0, cfg["vocab_size"], n)
    gaps = side["want"][:n].max(-1) - side["want"][np.arange(n), drawn]
    say(summary="garbage", tokens=n, gap_mean=float(gaps.mean()),
        gap_max_of_128=float(gaps[:128].max()), gap_min=float(gaps.min()),
        logit_spread=float(side["want"][:n].std(-1).mean()))

    def measure(name):
        got = job.served_side(ctx, net, side)
        d, p, f = got["decode_err"], got["prefill_err"], got["ffn_err"]
        pct = lambda a, q: float(np.percentile(a, q))
        say(variant=name, passes=job.judge(ctx, side, got),
            path_err_p10=pct(d, 10), path_err_median=pct(d, 50),
            path_err_mean=float(d.mean()), path_err_p90=pct(d, 90),
            path_err_max=float(d.max()), prefill_err=[float(v) for v in p],
            ffn_err_median=pct(f, 50), ffn_err_p90=pct(f, 90),
            ffn_err_max=float(f.max()),
            route_elsewhere=got["route_elsewhere"],
            allowed=[ref.PATH_ERR, ref.FFN_ERR, ref.ROUTE_ELSEWHERE])

    @jax.jit
    def to_int8(w):
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=1, keepdims=True) / 127.0
        return (jnp.round(f / scale) * scale).astype(w.dtype)

    def through_fp8(f, scale):
        # e4m3 by arithmetic (four significant bits, least exponent -6,
        # largest value 448), not by a convert pair the compiler may
        # fold away
        a = jnp.abs(f / scale)
        step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3)
        return jnp.sign(f) * jnp.round(a / step) * step * scale

    @jax.jit
    def to_fp8(w):
        f = w.astype(jnp.float32)
        return through_fp8(
            f, jnp.max(jnp.abs(f), axis=1, keepdims=True) / 448.0
        ).astype(w.dtype)

    def restore():
        """The experts as the seed made them (not kept twice)."""
        shapes = {k: tuple(p.value.shape) for k, p in params.items()}
        for k in experts:
            params[k].value = None
        gc.collect()
        made = builder.seeded_values(
            shapes, args.seed, dtype,
            (pcfg.hc_mult * pcfg.hidden_size) ** -0.5)
        for k in experts:
            params[k].value = made[k]

    # the served prefill's own choices, recorded by the run measured
    chosen, choose = [], xing4.moe_choose

    def recording(scores_, e_bias, top_k):
        idx = choose(scores_, e_bias, top_k)
        jax.debug.callback(lambda i: chosen.append(np.asarray(i)), idx,
                           ordered=True)
        return idx

    xing4.moe_choose = recording
    try:
        measure("bf16")
        jax.effects_barrier()
    finally:
        xing4.moe_choose = choose
    tokens = len(side["ids"])
    flips, decisive = [], []
    # the first prefill's records are [tokens, k], one an expert layer
    first = [c for c in chosen if len(c) == tokens][:len(side["chosen"])]
    for mine, theirs in zip(first, side["chosen"]):
        differs = (np.sort(mine, -1) != np.sort(theirs[:tokens], -1)).any(-1)
        flips.append(float(differs.mean()))
        decisive.append(float(differs[side["decode_rows"]].mean()))
    say(summary="routing", layers=len(flips),
        tokens_with_another_expert_set_share_by_layer=flips,
        same_share_at_the_decisive_rows=decisive,
        least_margin_of_the_decisive_rows=side["least_margin"],
        median_least_margin=side["least_margin_median"])

    # by reduce_precision, which the compiler may not fold away: a bf16
    # dot of bf16 operands accumulates in float32 on the chip, and with
    # excess precision allowed its rounded output is never made, so
    # "the scores computed in bfloat16" written as casts IS the float32
    # router there (PR 28 read the program's own numbers for it)
    scores = xing4.moe_scores
    bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=7)
    xing4.moe_scores = lambda h, w: bf16(jax.nn.sigmoid(bf16(jnp.dot(
        h.astype(jnp.float32), w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))))
    try:
        measure("router_bf16")
    finally:
        xing4.moe_scores = scores
    adopt = kv.adopt_into_pages
    kv.adopt_into_pages = lambda arena, blk, *a: adopt(
        arena, through_fp8(blk.astype(jnp.float32), jnp.max(
            jnp.abs(blk.astype(jnp.float32)), -1, keepdims=True) / 448.0
        ).astype(blk.dtype), *a)
    try:
        measure("cache_fp8")
    finally:
        kv.adopt_into_pages = adopt
    for name, rounder in (("experts_int8", to_int8), ("experts_fp8", to_fp8)):
        for k in experts:
            params[k].value = rounder(params[k].value)
        measure(name)
        restore()
    return 0


if __name__ == "__main__":
    sys.exit(main())
