"""lowered_text — the programs of the main path as StableHLO text, to
compare two checkouts without a chip.

    JAX_PLATFORMS=cpu python tools/lowered_text.py dump <checkout> <dir>

Writes one ``<program>.txt`` a program (``jax.jit(...).lower(...)
.as_text()``, locations stripped) for toy nets built from the
checkout: ``generation.decode_step`` through a page
table, ``generation.prefill`` (a whole block and a chunk at an
offset) and the slab decode step at per-row and at scalar positions,
for a GQA Llama (bf16 pages, int8 pages, int8 weights) and a Xing4;
the paged decode step and the block prefill of a Kimi-Linear that holds
half its experts (a latent page in one layer, a state a row in the
others); one ``CompiledTrainStep`` of a Llama with a tied (MHA) and an untied
(GQA) head. A change that claims to leave the programs alone gives
the same files.

    python tools/lowered_text.py diff <dir of the parent> <dir of the change>

says of each program whether the text is identical, holds the same
operations in another order (value names taken out, lines compared as
a multiset), or which operations only one side has.

    python tools/lowered_text.py digest <dump> <file.json>

writes a dump's record: of each program the SHA-256 of its text and the
number of ``stablehlo.case`` it holds (one a layer in a paged decode
step: the span ladder of ``quantization.kv.write_and_attend_paged``;
none anywhere else). ``tests/lowered_text.json`` is the record
``tests/test_lowered_text.py`` holds the tree to: a PR that means to
change a program writes it anew from its own dump, and one that does
not finds out there that it did.
"""
import argparse
import hashlib
import json
import os
import re
import sys


def diff(a, b):
    """Verdict a program of two dumps; 0 when no program holds an
    operation the other side lacks."""
    from collections import Counter

    names = re.compile(r"[%@][\w#.:]+")
    worst = 0
    for name in sorted(set(os.listdir(a)) | set(os.listdir(b))):
        texts = []
        for d in (a, b):
            path = os.path.join(d, name)
            texts.append(open(path).read() if os.path.exists(path) else "")
        if texts[0] == texts[1]:
            print(f"{name}: identical")
            continue
        ops = [Counter(names.sub("%", t).splitlines()) for t in texts]
        only = [sum((ops[i] - ops[1 - i]).values()) for i in (0, 1)]
        if only == [0, 0]:
            print(f"{name}: the same operations in another order")
            continue
        worst = 1
        print(f"{name}: {only[0]} operation(s) only in {a}, "
              f"{only[1]} only in {b}")
        for i, d in enumerate((a, b)):
            for line, n in sorted((ops[i] - ops[1 - i]).items()):
                print(f"  {'-+'[i]} x{n} {line.strip()[:160]}")
    return worst


def digest(dump):
    """``{program: {"sha256": ..., "cases": ...}}`` of a dump."""
    record = {}
    for name in sorted(os.listdir(dump)):
        text = open(os.path.join(dump, name)).read()
        record[name[:-len(".txt")]] = {
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "cases": len(re.findall(r"\bstablehlo\.case\b", text)),
        }
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("dump", "diff", "digest"))
    ap.add_argument("first", help="dump: the checkout; else: a dump")
    ap.add_argument("second", help="dump: where to write; diff: a dump; "
                                   "digest: the record to write")
    args = ap.parse_args()
    if args.mode == "diff":
        return diff(args.first, args.second)
    if args.mode == "digest":
        with open(args.second, "w") as f:
            json.dump(digest(args.first), f, indent=1)
            f.write("\n")
        return 0
    return dump_programs(os.path.abspath(args.first), args.second)


def dump_programs(root, out):
    sys.path.insert(0, root)
    os.makedirs(out, exist_ok=True)

    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import (KimiLinearConfig, KimiLinearForCausalLM,
                                   LlamaConfig, LlamaForCausalLM,
                                   Xing4Config, Xing4ForCausalLM)
    from paddle_tpu.models import generation as gen
    from paddle_tpu.quantization import quantize_for_serving

    def dump(name, fn, *a):
        text = jax.jit(fn).lower(*a).as_text()
        text = re.sub(r"\s*loc\([^\n]*\)$|^#loc[^\n]*\n", "", text,
                      flags=re.M)
        with open(os.path.join(out, name + ".txt"), "w") as f:
            f.write(text)
        print(f"{name}: {len(text.splitlines())} lines")

    B, P, PS, S_MAX, CHUNK = 4, 4, 8, 32, 16

    def serve_programs(tag, net, dtypes, only=None):
        cfg = net.config
        net.eval()
        params = {k: p.value for k, p in net.named_parameters()}
        buffers = {k: b.value for k, b in net.named_buffers()}

        def with_net(body):
            def run(params, buffers, *a):
                net.load_functional_state(params, buffers)
                return body(*a)
            return run

        tok = jnp.zeros((B, 1), jnp.int32)
        rows = jnp.arange(B, dtype=jnp.int32)
        tbl = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
        ids = jnp.zeros((1, CHUNK), jnp.int32)
        for dt in dtypes:
            arena = gen.alloc_kv_caches(cfg, B * P + 1, PS, dt, rows=B)
            slab = gen.alloc_kv_caches(cfg, B, S_MAX, dt)
            whole = gen.alloc_kv_caches(cfg, 1, CHUNK, dt)
            block = gen.alloc_kv_caches(cfg, 1, S_MAX, dt)
            for name, body, a in (
                ("decode_paged", lambda t, c, p, tb: gen.decode_step(
                    net, t, c, p, page_table=tb), (tok, arena, rows, tbl)),
                ("decode_rows", lambda t, c, p: gen.decode_step(
                    net, t, c, p), (tok, slab, rows)),
                ("decode_scalar", lambda t, c, p: gen.decode_step(
                    net, t, c, p), (tok, slab, jnp.int32(3))),
                ("prefill_block", lambda i, c, n: gen.prefill(
                    net, i, c, length=n), (ids, whole, jnp.int32(9))),
                ("prefill_chunk", lambda i, c, n, p: gen.prefill(
                    net, i, c, length=n, pos=p),
                 (ids, block, jnp.int32(9), jnp.int32(8))),
            ):
                if only is not None and name not in only:
                    continue
                dump(f"{tag}_{name}_{dt}", with_net(body), params, buffers,
                     *a)
        net.load_functional_state(params, buffers)

    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    serve_programs("llama_gqa", llama, ("bfloat16", "int8"))
    serve_programs("llama_gqa_w8", quantize_for_serving(llama),
                   ("bfloat16",))
    serve_programs("xing4", Xing4ForCausalLM(Xing4Config.tiny(
        hc_sinkhorn_iters=2)), ("bfloat16",))

    for tag, kw in (("tied_mha", {"tie_word_embeddings": True}),
                    ("untied_gqa", {"num_key_value_heads": 2})):
        cfg = LlamaConfig.tiny(**kw)
        net = LlamaForCausalLM(cfg)
        net.train()
        opt = paddle.optimizer.AdamW(
            1e-3, parameters=net.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        step = paddle.jit.CompiledTrainStep(
            net, lambda lg, lb: paddle.models.llama.causal_lm_loss(
                lg, lb).mean(), opt)
        x = paddle.to_tensor(np.zeros((2, 16), "int32"))
        step([x], [x])
        dump(f"train_{tag}", step._step_fn, *step._step_args_sds)

    # after every program PR 31 recorded, so that none of them moves
    serve_programs("kimi_linear", KimiLinearForCausalLM(
        KimiLinearConfig.tiny(experts_first=8, experts_held=8)),
        ("bfloat16",), only=("decode_paged", "prefill_block"))


if __name__ == "__main__":
    sys.exit(main())
