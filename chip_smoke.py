#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the two things users of this framework do — take train steps and
serve requests — once, end to end, on one TPU chip, through the public
API (``import paddle_tpu as paddle``), at Llama-2-7B's published widths
(hidden 4096, 32 heads x 128, intermediate 11008, vocab 32000) cut in
depth only, with random weights made from a seed:

- **kernels** — every Pallas kernel in ``paddle_tpu/kernels`` runs
  compiled (never interpreted) at those widths against the composed
  reference that lives in the same file;
- **train** — ``LlamaForCausalLM`` + ``AdamW`` + ``CompiledTrainStep``
  (AMP O2 bf16), B=4 x S=1024, five steps on one repeated batch: the
  loss is finite every step and lower at step 5 than at step 1;
- **serve** — ``PagedServingEngine`` (bf16 weights, bf16 KV pages,
  prefix cache) behind ``ServingFrontend``; six ``POST /v1/generate``
  SSE streams over HTTP, each token-exact against ``net.generate()``;
  then one more stream on int8 weights + int8 KV pages.

``--multichip`` runs ONLY the four-chip phase and what it is compared
with: the Fleet hybrid path (dp2 x mp2, TP layers under the default
layout policy, ``CompiledTrainStep``) against the same seed and batch
on one of the four devices.

One process. No CPU branch, no small-model branch, no interpret mode:
without a TPU the script exits non-zero before it builds anything. It
exits non-zero at the first failed phase. Every phase prints one JSON
line; the LAST line of stdout is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phases take their configuration as arguments, so a scratch script
can rehearse them on the CPU at a toy size; ``main()`` fixes the sizes.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Depth cuts, from ``compiled.memory_analysis()`` of each whole program
# compiled for a described v5e chip (16 GiB HBM) in the sandbox:
# the train step is 10.3 GiB at 2 layers, 13.4 GiB at 3 and does not fit
# at 4 — 3 is the largest depth under 14 GiB; the depth-8 bf16 decode
# step over a B=8 x 2048-token page arena is 5.7 GiB.
TRAIN_DEPTH = 3
SERVE_DEPTH = 8

# Tolerances. Kernels: the float-rounding tolerance each kernel's tier-1
# test states (the bit-exact pins there hold between the interpreted
# kernel and its reference on one backend; a Mosaic kernel and an XLA
# program round in different orders). ``atol`` is stated for outputs of
# unit scale, so it is scaled by the reference's RMS where that is
# larger than 1.
FP32_RMS_FWD = (2e-5, 2e-5)      # tests/test_fused_llama.py
FP32_RMS_BWD = (1e-4, 1e-5)
FP32_ROPE = (1e-5, 1e-5)
BF16_ATTN = (3e-2, 3e-2)         # flash against composed, bf16
# Serving: where a served stream leaves net.generate()'s, both tokens
# must sit within this share of the largest |logit| of the top of the
# reference distribution at that position — four bf16 ulps — or the
# stream is wrong rather than a rounding tie.
TIE_TOL = 4 * 2.0 ** -8
# --multichip: loss of the dp2 x mp2 step against one device, per step.
# Same math, bf16 operands, a different reduction tree per matmul.
MULTICHIP_RTOL = 2e-2


class _CompileMeter:
    """What XLA's compiler and the persistent compile cache did since
    the last phase line, from ``jax.monitoring``. A phase's
    ``compile_s`` is wall time to the first result — tracing and
    lowering in Python included, which no cache saves; ``xla_compile_s``
    is the compiler's own share, the part a warm cache takes away."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "xla_compile_s",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        import jax

        self.totals = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(
            lambda event, **kw: self._add(event, 1))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self._add(event, secs))

    def _add(self, event, amount):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += amount

    def take(self):
        out = {k: round(v, 3) for k, v in self.totals.items()}
        self.totals = dict.fromkeys(self.totals, 0)
        return out


_METER = None   # main() starts it once jax is known to see a TPU


def _emit(phase, **fields):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": phase, **fields, **(_METER.take() if _METER else {}),
        "bytes_in_use": stats.get("bytes_in_use"),
        # the process's high-water mark so far, not this phase's alone
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }), flush=True)


def _note(msg):
    """Progress, on stderr: says how far a run got if the process dies."""
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _timed(fn, *args, steady=3):
    """``(result, compile_s, steady_s)``: the first call's wall time
    (tracing and compiling included), then the median of ``steady``
    more; each call ends in ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    laps = []
    for _ in range(steady):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(time.perf_counter() - t0)
    return out, first, sorted(laps)[len(laps) // 2]


def _assert_close(name, got, ref, tol):
    import numpy as np

    rtol, atol = tol
    g = np.asarray(got, np.float32)
    r = np.asarray(ref, np.float32)
    assert g.shape == r.shape, (name, g.shape, r.shape)
    assert np.isfinite(g).all(), f"{name}: non-finite output"
    scale = max(1.0, float(np.sqrt(np.mean(np.square(r)))))
    excess = np.abs(g - r) - (atol * scale + rtol * np.abs(r))
    assert excess.max() <= 0, (
        f"{name}: off its reference by {float(np.abs(g - r).max()):.3g} "
        f"(rtol {rtol}, atol {atol} x scale {scale:.3g})"
    )


# ------------------------------------------------------------- kernels
def _kernel_cases(cfg, batch, seq, decode_rows, ctx):
    """``(name, fused, reference, args, tolerance)`` for every kernel,
    at this config's widths. ``fused`` is jittable; ``reference`` is
    called as it is (jitted here where it runs on the device)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import (
        flash_attention as fa,
        rms_norm as rn,
        rope as rp,
    )

    rng = np.random.default_rng(0)
    hid, heads, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim

    def arr(shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    def rms_ref(x, w):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(ms + 1e-6) * w).astype(x.dtype)

    def rope_ref(x, cos, sin):
        # on the host: XLA's TPU compiler aborts (fusion_emitter.cc,
        # IsFusibleUnalignedDUS) on this half-width concatenate in fp32
        x, cos, sin = (np.asarray(a, np.float32) for a in (x, cos, sin))
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        return np.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def rope_bwd_ref(x, cos, sin):
        # d/dx sum(sin(rope(x))): the inverse rotation of cos(rope(x))
        return rope_ref(np.cos(rope_ref(x, cos, sin)), cos, -np.asarray(sin))

    def grads(f, argnums):
        # a sin() on top keeps the cotangent from being all ones
        return jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32))),
            argnums=argnums)

    cases = []
    # 338 rows: a prompt length no multiple-of-8 block divides
    for tag, rows in (("train", (batch, seq)), ("decode", (decode_rows, 1)),
                      ("338 rows", (1, 338))):
        x, w = arr(rows + (hid,)), arr((hid,))
        cases.append((f"rms_norm fwd {tag}",
                      lambda x, w: rn.rms_norm_fused(x, w, 1e-6),
                      jax.jit(rms_ref), (x, w), FP32_RMS_FWD))
        cases.append((f"rms_norm bwd {tag}",
                      grads(lambda x, w: rn.rms_norm_fused(x, w, 1e-6),
                            (0, 1)),
                      jax.jit(grads(rms_ref, (0, 1))), (x, w),
                      FP32_RMS_BWD))
    cos, sin = rp.build_rope_cache(seq, hd)
    x = arr((batch, seq, heads, hd))
    cases.append(("rope fwd", rp.rope_fused, rope_ref, (x, cos, sin),
                  FP32_ROPE))
    cases.append(("rope bwd", grads(rp.rope_fused, 0), rope_bwd_ref,
                  (x, cos, sin), FP32_ROPE))
    # per-row decode: every batch row reads its own table row
    full_cos, full_sin = rp.build_rope_cache(ctx, hd)
    at = jnp.asarray(rng.integers(0, ctx, (decode_rows,)))
    cases.append(("rope per-row decode", rp.rope_fused, rope_ref,
                  (arr((decode_rows, 1, heads, hd)),
                   full_cos[0, at][:, None], full_sin[0, at][:, None]),
                  FP32_ROPE))
    flash_s = 2 * seq
    q, k, v = (arr((2, flash_s, heads, hd), jnp.bfloat16) for _ in "qkv")
    assert fa._select(q, k, v, True)[0], (
        "flash selection did not pick the Pallas kernel: "
        + fa._select(q, k, v, True)[2])
    scale = 1.0 / hd ** 0.5

    def flash(q, k, v):
        return fa.flash_attention_fwd(q, k, v, causal=True)

    def flash_ref(q, k, v):
        return fa._composed(q, k, v, causal=True, scale=scale)

    cases.append((f"flash fwd S={flash_s}", flash, jax.jit(flash_ref),
                  (q, k, v), BF16_ATTN))
    cases.append((f"flash bwd S={flash_s}", grads(flash, (0, 1, 2)),
                  jax.jit(grads(flash_ref, (0, 1, 2))), (q, k, v),
                  BF16_ATTN))
    return cases


def phase_kernels(cfg, *, batch, seq, decode_rows, ctx):
    import jax

    rows = []
    for name, fused, ref, args, tol in _kernel_cases(
            cfg, batch, seq, decode_rows, ctx):
        _note(f"kernel {name}")
        got, compile_s, steady_s = _timed(jax.jit(fused), *args)
        want = ref(*args)
        for i, (g, r) in enumerate(zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(want))):
            _assert_close(f"{name}[{i}]", g, r, tol)
        rows.append({"kernel": name, "compile_s": round(compile_s, 3),
                     "steady_s": round(steady_s, 6)})
        del got, want
    _emit("kernels",
          compile_s=round(sum(r["compile_s"] for r in rows), 3),
          steady_s=round(sum(r["steady_s"] for r in rows), 6),
          kernels=rows)


# --------------------------------------------------------------- train
def _batch(cfg, batch, seq, seed=0):
    import numpy as np

    import jax.numpy as jnp

    ids = np.random.RandomState(seed).randint(0, cfg.vocab_size,
                                              (batch, seq))
    return jnp.asarray(ids, jnp.int32)


def _train_steps(paddle, net, loss_fn, ids, steps, place=lambda a: a):
    """``steps`` compiled AdamW steps on one repeated batch; returns
    (losses, compile_s, steady_s, trainer)."""
    opt = paddle.optimizer.AdamW(1e-4, parameters=net.parameters())
    step = paddle.jit.CompiledTrainStep(
        net, loss_fn, opt, amp_level="O2", amp_dtype="bfloat16")
    x = paddle.Tensor(place(ids))
    losses, laps = [], []
    for i in range(steps):
        _note(f"train step {i + 1}/{steps}")
        t0 = time.perf_counter()
        loss, _ = step([x], [x])
        losses.append(float(loss.numpy()))   # blocks on the step
        laps.append(time.perf_counter() - t0)
    # lower median: a mesh run may compile once more on its second
    # step, when the first step's outputs come back with their
    # steady-state placements
    rest = sorted(laps[1:])
    steady = rest[(len(rest) - 1) // 2]
    return losses, laps[0] - steady, steady, step


def phase_train(cfg, *, batch, seq, steps=5):
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    net = paddle.models.LlamaForCausalLM(cfg)

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    losses, compile_s, steady_s, _ = _train_steps(
        paddle, net, loss_fn, _batch(cfg, batch, seq), steps)
    assert np.isfinite(losses).all(), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    _emit("train", compile_s=round(compile_s, 3),
          steady_s=round(steady_s, 4), layers=cfg.num_hidden_layers,
          n_params=net.num_params(), batch=batch, seq=seq, losses=losses)


# --------------------------------------------------------------- serve
def _stream(port, prompt, max_new):
    from paddle_tpu.serving import stream_generate

    events, _ = stream_generate(
        "127.0.0.1", port,
        {"input_ids": [int(t) for t in prompt],
         "max_new_tokens": int(max_new)}, timeout=900.0)
    assert events and events[-1][0] == "done", (
        f"stream did not end DONE: {events[-1:]}")
    assert events[-1][1]["status"] == "DONE", events[-1]
    return [d["token"] for e, d in events if e == "token"]


def _check_stream(net, prompt, served, want, cache_dtype):
    """Token-exact against ``net.generate()``, or — where the chip's
    rounding split a tie — the first diverging position with both
    tokens inside ``TIE_TOL`` of the reference's top logit there.
    Returns None when exact, else the divergence record."""
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.models.generation import alloc_kv_caches, prefill

    if served == want:
        return None
    assert len(served) == len(want), (len(served), len(want))
    at = next(i for i, (a, b) in enumerate(zip(served, want)) if a != b)
    # generate()'s own prefill over the shared context gives the
    # reference distribution at the position the streams part
    ctx = np.concatenate([prompt, served[:at]]).astype(np.int32)[None]
    logits, _ = prefill(
        net, jnp.asarray(ctx),
        alloc_kv_caches(net.config, 1, ctx.shape[1], cache_dtype))
    lg = np.asarray(logits, np.float32).reshape(-1)
    top = float(lg.max())
    gap = max(top - float(lg[served[at]]), top - float(lg[want[at]]))
    rec = {"position": at, "served": served[at], "generate": want[at],
           "logit_gap": gap, "allowed": TIE_TOL * float(np.abs(lg).max())}
    assert gap <= rec["allowed"], (
        f"served stream left net.generate() at position {at} and it is "
        f"no rounding tie: {rec}")
    return rec


def phase_serve(cfg, *, batch_size, max_seq, page_size, min_bucket,
                prompt_lens, shared_prefix, max_new):
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedServingEngine, ServingFrontend

    paddle.seed(1)
    net = paddle.models.LlamaForCausalLM(cfg)
    net.to(dtype="bfloat16")
    net.eval()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in prompt_lens]
    # the last request repeats the one before it for its first
    # ``shared_prefix`` tokens: the prefix cache adopts those pages
    prompts[-1][:shared_prefix] = prompts[-2][:shared_prefix]

    def reference(model, prompt, cache_dtype):
        out = model.generate(paddle.Tensor(jnp.asarray(prompt[None])),
                             max_new_tokens=max_new,
                             cache_dtype=cache_dtype)
        return [int(t) for t in np.asarray(out.numpy())[0][len(prompt):]]

    def serve(model, cache_dtype, waves, **engine_kw):
        """Streams each wave's prompts concurrently over HTTP; returns
        (per-request token lists, warmup seconds, serving seconds,
        engine stats). Warmup compiles the programs of the prompt
        buckets this traffic lands in, not the whole ladder up to
        ``max_seq``: the rest would only be compiled, never run."""
        engine = PagedServingEngine(
            model, max_batch_size=batch_size, max_seq_len=max_seq,
            page_size=page_size, min_bucket=min_bucket,
            cache_dtype=cache_dtype, **engine_kw)
        _note(f"serve {cache_dtype}: warmup")
        t0 = time.perf_counter()
        warm = engine.warmup(buckets=sorted({
            engine.pool.bucket_for(len(prompts[i]))
            for wave in waves for i in wave}))
        warm_s = time.perf_counter() - t0
        arena_leaf = engine._flat[0]
        fe = ServingFrontend(engine).start()
        out, errors = {}, []

        def one(i):
            try:
                out[i] = _stream(fe.port, prompts[i], max_new)
            except BaseException as e:  # re-raised on the main thread
                errors.append(e)

        t0 = time.perf_counter()
        try:
            for wave in waves:
                _note(f"serve {cache_dtype}: streams {wave}")
                threads = [threading.Thread(target=one, args=(i,))
                           for i in wave]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=900)
                    assert not t.is_alive(), "a stream never ended"
                if errors:
                    raise errors[0]
            serve_s = time.perf_counter() - t0
            # the decode step DONATES the arena: the buffer the engine
            # held before traffic is gone, so the donated programs ran
            assert getattr(arena_leaf, "q", arena_leaf).is_deleted(), (
                "the KV arena was not donated to the decode step")
            pool = engine.page_pool.stats()
            cache = (engine.prefix_cache.stats()
                     if engine.prefix_cache is not None else None)
            held = cache["cached_pages"] if cache else 0
            assert pool["pages_in_use"] == held, (
                f"page leak: {pool['pages_in_use']} in use, "
                f"{held} held by the prefix cache")
        finally:
            fe.stop(close_engine=True)
        pool = engine.page_pool.stats()
        assert (pool["pages_in_use"] == 0
                and pool["claims"] == pool["releases"]), (
            f"page accounting drift after close: {pool}")
        return out, warm_s, serve_s, {"warmup": warm, "prefix": cache,
                                      "pool": pool}

    # -- bf16 weights, bf16 KV pages, prefix cache on: every stream
    # token-exact against net.generate()
    last = len(prompts) - 1
    served, warm_s, serve_s, stats = serve(
        net, "bfloat16", [list(range(last)), [last]], prefix_cache=True)
    assert stats["prefix"]["hits"] >= 1 and \
        stats["prefix"]["tokens_saved"] >= shared_prefix, (
        f"the shared prefix was not adopted: {stats['prefix']}")
    t0 = time.perf_counter()
    ties = []
    for i, prompt in enumerate(prompts):
        _note(f"serve: net.generate() for request {i}")
        want = reference(net, prompt, "bfloat16")
        tie = _check_stream(net, prompt, served[i], want, "bfloat16")
        if tie is not None:
            ties.append({"request": i, **tie})
    ref_s = time.perf_counter() - t0
    _emit("serve", compile_s=round(warm_s, 3), steady_s=round(serve_s, 3),
          reference_s=round(ref_s, 3), layers=cfg.num_hidden_layers,
          requests=len(prompts), new_tokens=max_new,
          exact=len(prompts) - len(ties), rounding_ties=ties,
          programs=stats["warmup"]["programs"], prefix=stats["prefix"],
          peak_pages=stats["pool"]["peak_pages_in_use"])

    # -- the quantized flavour, once: int8 weights + int8 KV pages
    qnet = paddle.quantization.quantize_for_serving(net, inplace=True)
    served, warm_s, serve_s, stats = serve(qnet, "int8", [[0]])
    want = reference(qnet, prompts[0], "int8")
    tie = _check_stream(qnet, prompts[0], served[0], want, "int8")
    _emit("serve_int8", compile_s=round(warm_s, 3),
          steady_s=round(serve_s, 3), exact=tie is None,
          rounding_ties=[tie] if tie else [],
          programs=stats["warmup"]["programs"])


# ----------------------------------------------------------- multichip
def phase_multichip(cfg, *, batch, seq, steps=3):
    """dp2 x mp2 hybrid train steps on all four devices, then the same
    seed and batch on one of them."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology,
        HybridCommunicateGroup,
    )
    from paddle_tpu.parallel import layout, mesh as mesh_mod

    devices = jax.devices()
    assert len(devices) == 4, f"--multichip needs 4 devices: {devices}"
    dp = mp = 2
    hcg = HybridCommunicateGroup(CommunicateTopology(
        ["dp", "pp", "sharding", "sep", "mp"], [dp, 1, 1, 1, mp]))
    ids = _batch(cfg, batch, seq)

    paddle.seed(0)
    pipe = paddle.models.LlamaForCausalLMPipe(cfg, num_stages=1)
    # the one-device run starts from these very weights
    init = {k: np.asarray(p.value)
            for k, p in pipe.to_causal_lm().named_parameters()}
    losses, compile_s, steady_s, step = _train_steps(
        paddle, pipe, lambda out, lbl: pipe._loss_fn(out, lbl), ids, steps,
        place=lambda a: jax.device_put(a, NamedSharding(
            hcg.mesh, layout.get_policy().batch_spec(2))))
    assert np.isfinite(losses).all(), f"non-finite loss: {losses}"

    # code that has never seen more than one chip may put everything on
    # the first: the mp-sharded families must lie on four devices, each
    # holding 1/mp of the whole (dp replicates)
    pol = layout.get_policy()
    sharded = 0
    for name, p in pipe.named_parameters():
        arr = p.value
        if pol.mp_axis not in str(getattr(arr.sharding, "spec", "")):
            continue
        shards = arr.addressable_shards
        assert len({s.device for s in shards}) == 4, (name, arr.sharding)
        for s in shards:
            assert s.data.nbytes * mp == arr.nbytes, (
                name, s.data.nbytes, arr.nbytes)
        sharded += 1
    # q,k,v,o,gate,up,down per block + embedding + head
    assert sharded == 7 * cfg.num_hidden_layers + 2, sharded
    compiled = step._step_fn.lower(*step._step_args_sds).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo, "no all-reduce in the compiled hybrid step"
    mem = compiled.memory_analysis()
    _emit("multichip_dp2xmp2", compile_s=round(compile_s, 3),
          steady_s=round(steady_s, 4), layers=cfg.num_hidden_layers,
          batch=batch, seq=seq, losses=losses, mp_sharded_params=sharded,
          all_reduce_ops=(hlo.count("all-reduce(")
                          + hlo.count("all-reduce-start(")),
          per_device_program_bytes=(
              mem.argument_size_in_bytes + mem.output_size_in_bytes
              + mem.temp_size_in_bytes - mem.alias_size_in_bytes))

    del pipe, step, compiled
    gc.collect()
    mesh_mod.set_mesh(None)   # what follows is a one-device program
    with paddle.LazyGuard():
        net = paddle.models.LlamaForCausalLM(cfg)
    for k, p in net.named_parameters():
        p.value = jax.device_put(init.pop(k), devices[0])

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    one, compile_s, steady_s, _ = _train_steps(
        paddle, net, loss_fn, ids, steps,
        place=lambda a: jax.device_put(a, devices[0]))
    _emit("multichip_one_device", compile_s=round(compile_s, 3),
          steady_s=round(steady_s, 4), losses=one)
    np.testing.assert_allclose(
        losses, one, rtol=MULTICHIP_RTOL,
        err_msg="dp2 x mp2 loss trajectory left the one-device run's")


# ---------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip dp2 x mp2 phase and "
                         "its one-device comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (devices: {jax.devices()}); "
              "this script has no CPU branch", file=sys.stderr)
        return 2
    import paddle_tpu as paddle
    from paddle_tpu.kernels import autotune

    assert not autotune.interpret_mode(), "kernels would be interpreted"
    global _METER
    _METER = _CompileMeter()
    cache_dir = paddle.jit.place_compile_cache()
    print(json.dumps({"phase": "start", "compile_cache": cache_dir,
                      "cache_entries": len(os.listdir(cache_dir))
                      if os.path.isdir(cache_dir) else 0}), flush=True)
    LlamaConfig = paddle.models.LlamaConfig

    if args.multichip:
        phase_multichip(
            LlamaConfig.llama2_7b(num_hidden_layers=TRAIN_DEPTH,
                                  max_position_embeddings=1024),
            batch=4, seq=1024)
    else:
        phase_kernels(LlamaConfig.llama2_7b(), batch=4, seq=1024,
                      decode_rows=8, ctx=2048)
        phase_train(
            LlamaConfig.llama2_7b(num_hidden_layers=TRAIN_DEPTH,
                                  max_position_embeddings=1024),
            batch=4, seq=1024)
        gc.collect()   # the trainer is gone: phases do not share HBM
        phase_serve(
            LlamaConfig.llama2_7b(num_hidden_layers=SERVE_DEPTH,
                                  max_position_embeddings=2048),
            batch_size=8, max_seq=2048, page_size=16, min_bucket=64,
            prompt_lens=(64, 200, 512, 64, 320, 320), shared_prefix=256,
            max_new=32)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
