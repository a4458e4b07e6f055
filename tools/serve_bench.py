"""Offline serving benchmark: replay a synthetic Poisson trace.

Drives ``paddle_tpu.serving.ServingEngine`` (or, with ``--paged``, the
page-pool ``PagedServingEngine``) with a reproducible open-loop request
trace (exponential inter-arrivals at ``--rate`` req/s, uniform
prompt/decode lengths) against a tiny CPU Llama by default, and reports
throughput plus latency percentiles from the engine's own metrics. The
point is to exercise the ENGINE — admission under load, slot churn,
backpressure — end to end without hardware; point
``--hidden/--layers/--heads`` at a real config on a chip for actual
numbers.

    python tools/serve_bench.py --requests 32 --rate 50 --max-batch 4
    python tools/serve_bench.py --paged --page-size 8 --http

``--http`` replays the SAME trace through the streaming HTTP/SSE
front-end over localhost — every request is a real POST + SSE stream on
its own thread, so the JSON record carries WIRE-level TTFT/ITL (client-
measured, socket included) next to the engine's in-process numbers,
plus the page-pool occupancy/exhaustion counters.

``--fleet N`` goes one tier up: N replica SUBPROCESSES on ephemeral
ports behind the occupancy-aware ``FleetRouter``, the trace replayed
through the router — the record carries per-replica occupancy and
request counts next to aggregate throughput (``--fleet-prefill`` adds
a cross-process prefill-pool worker).

Open-loop means arrivals do not wait for completions: when the engine
falls behind, the queue grows and (past ``--max-queue``) requests are
REJECTED — that backpressure shows up in the report rather than being
hidden by a closed-loop driver.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_trace(n, rate, seed, vocab, prompt_lo, prompt_hi, new_lo,
                new_hi, slo_class="interactive"):
    """[(arrival_s, prompt ids, max_new, slo_class)] — Poisson
    arrivals, uniform lengths; fully determined by ``seed``."""
    import numpy as np

    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n):
        L = int(rng.randint(prompt_lo, prompt_hi + 1))
        m = int(rng.randint(new_lo, new_hi + 1))
        trace.append((float(arrivals[i]), rng.randint(0, vocab, (1, L)),
                      m, slo_class))
    return trace


# --mix scenario names -> the SLO class their requests are tagged with
MIX_SCENARIOS = ("chat", "rag", "batch", "agent")


def build_mix_trace(mix, n, rate, seed, vocab, prompt_lo, prompt_hi,
                    new_lo, new_hi):
    """Named scenario mix: ``mix`` is a comma list from
    ``chat,rag,batch,agent``; ``n`` requests are split evenly across the
    named scenarios, each with its own arrival SHAPE (not just its own
    rate), then merged into one arrival-sorted open-loop trace:

    - ``chat`` (class ``interactive``): multi-turn sessions — 3 turns
      per session, turns spaced a few token-times apart, each turn's
      prompt longer than the last (the growing conversation context);
    - ``rag`` (class ``rag``): shared-prefix bursts — one retrieval
      context per burst, 4 near-simultaneous requests over it (the
      prefix-cache shape);
    - ``batch`` (class ``batch``): a flash-crowd ramp — arrivals
      concentrated toward the tail of the horizon, the thundering-herd
      shape that overruns admission;
    - ``agent`` (class ``agent``): steady Poisson tool-loop turns.

    Deterministic in ``seed``."""
    import numpy as np

    names = [s.strip() for s in str(mix).split(",") if s.strip()]
    if not names:
        raise SystemExit("--mix needs at least one scenario name")
    for s in names:
        if s not in MIX_SCENARIOS:
            raise SystemExit(
                f"unknown --mix scenario {s!r} "
                f"(known: {', '.join(MIX_SCENARIOS)})"
            )
    rng = np.random.RandomState(seed)
    horizon = n / max(rate, 1e-6)  # nominal trace duration, seconds
    share = max(1, n // len(names))
    events = []

    def prompt(length):
        length = int(max(prompt_lo, min(prompt_hi, length)))
        return rng.randint(0, vocab, (1, length))

    for name in names:
        k = share
        if name == "chat":
            turns = 3
            sessions = max(1, k // turns)
            for _ in range(sessions):
                start = float(rng.uniform(0.0, horizon * 0.8))
                base = int(rng.randint(prompt_lo, prompt_hi + 1))
                for t in range(turns):
                    gap = float(rng.exponential(
                        max(0.5 / rate, 1e-3))) * (t + 1)
                    events.append((
                        start + t * gap,
                        prompt(base + 4 * t),  # context grows per turn
                        int(rng.randint(new_lo, new_hi + 1)),
                        "interactive",
                    ))
        elif name == "rag":
            burst_sz = 4
            bursts = max(1, k // burst_sz)
            for _ in range(bursts):
                start = float(rng.uniform(0.0, horizon * 0.9))
                # one retrieval context, shared verbatim by the burst
                ctx = prompt(prompt_hi)
                for j in range(burst_sz):
                    ids = ctx.copy()
                    if ids.shape[1] > 1:
                        # distinct question tail on the shared context
                        ids[0, -1] = int(rng.randint(0, vocab))
                    events.append((
                        start + j * 0.002,
                        ids,
                        int(rng.randint(new_lo, new_hi + 1)),
                        "rag",
                    ))
        elif name == "batch":
            for _ in range(k):
                # sqrt ramp: density grows linearly toward the tail
                u = float(rng.uniform())
                events.append((
                    horizon * (0.5 + 0.5 * (u ** 0.5)),
                    prompt(int(rng.randint(prompt_lo, prompt_hi + 1))),
                    int(rng.randint(new_lo, new_hi + 1)),
                    "batch",
                ))
        else:  # agent: steady poisson over the whole horizon
            gaps = rng.exponential(horizon / max(k, 1), size=k)
            t_at = np.minimum(np.cumsum(gaps), horizon)
            for t in t_at:
                events.append((
                    float(t),
                    prompt(int(rng.randint(prompt_lo, prompt_hi + 1))),
                    int(rng.randint(new_lo, new_hi + 1)),
                    "agent",
                ))
    events.sort(key=lambda e: e[0])
    return events


def make_engine(args, net, speculative=None):
    from paddle_tpu.serving import PagedServingEngine, ServingEngine

    if args.paged:
        return PagedServingEngine(
            net, max_batch_size=args.max_batch, max_seq_len=args.max_seq,
            cache_dtype=args.cache_dtype, min_bucket=args.min_bucket,
            max_queue_size=args.max_queue, page_size=args.page_size,
            num_pages=args.num_pages, speculative=speculative,
            demand_paging=getattr(args, "demand_paging", None),
        )
    return ServingEngine(
        net, max_batch_size=args.max_batch, max_seq_len=args.max_seq,
        cache_dtype=args.cache_dtype, min_bucket=args.min_bucket,
        max_queue_size=args.max_queue, speculative=speculative,
    )


def parse_speculate(tokens):
    """``['draft=self:2', 'k=4']`` -> ``{'draft': ('self', 2), 'k': 4}``.

    ``draft=self:<N>`` runs the target's own first N layers as the
    draft (no extra weights); ``draft=tiny:<L>`` builds a fresh
    L-layer half-width draft sharing the vocab."""
    spec = {"k": 4, "draft": ("self", 1)}
    for t in tokens:
        key, _, val = t.partition("=")
        if key == "k":
            spec["k"] = int(val)
        elif key == "draft":
            kind, _, n = val.partition(":")
            if kind not in ("self", "tiny"):
                raise SystemExit(
                    f"--speculate draft must be self:<N> or tiny:<L>, "
                    f"got {val!r}"
                )
            spec["draft"] = (kind, int(n or 1))
        else:
            raise SystemExit(f"unknown --speculate key {key!r}")
    return spec


def make_speculative(args, cfg):
    """Build the SpeculativeDecoder for ``--speculate`` (None when
    off)."""
    if not getattr(args, "speculate", None):
        return None
    from paddle_tpu.serving import SpeculativeDecoder

    spec = parse_speculate(args.speculate)
    kind, n = spec["draft"]
    if kind == "self":
        return SpeculativeDecoder(exit_layer=n, k=spec["k"])
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(args.seed + 1)
    dcfg = LlamaConfig.tiny(
        vocab_size=cfg.vocab_size,
        hidden_size=max(cfg.hidden_size // 2, 8),
        intermediate_size=max(cfg.hidden_size, 16),
        num_hidden_layers=n,
        num_attention_heads=max(cfg.num_attention_heads // 2, 1),
    )
    draft = LlamaForCausalLM(dcfg)
    draft.eval()
    return SpeculativeDecoder(draft, k=spec["k"])


def zero_from_layer(net, n):
    """Zero ``o_proj``/``down_proj`` of every decoder layer >= ``n``:
    with both residual branches producing exact zeros those layers
    pass the hidden state through UNTOUCHED, so a ``draft=self:<n>``
    speculator is bitwise the target (full acceptance). This is the
    upper-bound shape ``make spec-smoke`` uses to demonstrate the
    mechanical win on CPU without training a real draft."""
    import jax.numpy as jnp

    for i, layer in enumerate(net.model.layers):
        if i < n:
            continue
        for lin in (layer.self_attn.o_proj, layer.mlp.down_proj):
            lin.weight.set_value(jnp.zeros_like(lin.weight.value))


def run_bench(args):
    import numpy as np  # noqa: F401

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=2 * args.hidden, num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    if getattr(args, "zero_from_layer", None) is not None:
        zero_from_layer(net, args.zero_from_layer)
    engine = make_engine(args, net, make_speculative(args, cfg))
    if getattr(args, "mix", None):
        trace = build_mix_trace(
            args.mix, args.requests, args.rate, args.seed, args.vocab,
            args.prompt_min, args.prompt_max, args.new_min, args.new_max,
        )
    else:
        trace = build_trace(
            args.requests, args.rate, args.seed, args.vocab,
            args.prompt_min, args.prompt_max, args.new_min, args.new_max,
        )

    # warmup: compile the decode step + the prompt buckets off the clock
    if args.warmup:
        # the full fixed-shape inventory (decode, every bucket's
        # prefill/adopt, gather/chunk, speculative programs) — this
        # also fills engine.program_memory, the per-program peak-bytes
        # table the record carries
        engine.warmup()
        for bucket in sorted({
            engine.pool.bucket_for(p.shape[1]) for _, p, _, _ in trace
        }):
            # largest prompt length that still lands in `bucket` AND
            # leaves room for the 2 warmup tokens under max_seq (a
            # full-bucket prompt at bucket == max_seq would be REJECTED
            # as too_long and silently skip the compile)
            L = min(bucket, args.max_seq - 2)
            if engine.pool.bucket_for(L) != bucket:
                continue  # bucket unreachable under max_seq; real
                # requests in it would be rejected too
            h = engine.submit(
                np.full((1, L), int(trace[0][1][0, 0]), np.int32), 2
            )
            engine.run_until_idle()
            assert h.status == "DONE", (
                f"warmup request for bucket {bucket} ended "
                f"{h.status} ({h.reason}) — compile not warmed"
            )
        # warmup tokens must not pollute the report
        engine.metrics = type(engine.metrics)()
        if engine.speculative is not None:
            engine.speculative.reset_stats()

    peak_active = 0
    if args.http:
        handles, wall, wire, peak_active = run_http_trace(engine, trace)
    else:
        wire = None
        t0 = time.monotonic()
        pending = list(trace)
        handles = []
        while pending or engine.scheduler.depth or engine.active_slots:
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                _, ids, m, cls = pending.pop(0)
                handles.append(engine.submit(ids, m, slo_class=cls))
            if engine.scheduler.depth or engine.active_slots:
                engine.step()
                peak_active = max(peak_active, engine.active_slots)
            elif pending:
                time.sleep(min(0.001, pending[0][0] - now))
        wall = time.monotonic() - t0

    rep = engine.metrics.report()
    done = sum(1 for h in handles if h.status == "DONE")
    out = {
        "requests": args.requests,
        "rate_req_s": args.rate,
        "mode": "http" if args.http else "in-process",
        "engine": type(engine).__name__,
        "wall_s": round(wall, 3),
        "completed": done,
        "rejected": rep["counters"]["rejected"],
        "timeouts": rep["counters"]["timeouts"],
        "tokens_out": rep["counters"]["tokens_out"],
        "decode_tok_s": round(rep["counters"]["tokens_out"] / wall, 1),
        "req_s": round(done / wall, 2),
        "engine_steps": engine.step_count,
        "cache_dtype": str(engine.cache_dtype),
        "pool": engine.pool.stats(),
        "metrics": rep,
    }
    out["peak_active_requests"] = peak_active
    if getattr(args, "mix", None):
        out["mix"] = args.mix
        out["mix_classes"] = sorted({cls for _, _, _, cls in trace})
    # per-class SLO attainment table straight off the labeled latency
    # histograms (warmup was excluded above by the metrics reset)
    from paddle_tpu.observability.slo import attainment_report

    out["slo"] = attainment_report()
    mem = engine.memory_report()
    if mem is not None:
        # the warmup-time HBM footprint table: estimated peak resident
        # bytes per compiled program (memory_lint live-range model),
        # with XLA memory_analysis + drift verdicts where available
        out["memory"] = mem
    if engine.speculative is not None:
        out["speculative"] = engine.speculative.stats()
        # the user-visible form of the win: PER-REQUEST acceptance
        # length (emitted tokens per verify launch) and per-request
        # decode throughput over the completed population
        acc = [h.spec_emitted / h.spec_rounds for h in handles
               if getattr(h, "spec_rounds", 0)]
        tps = []
        for h in handles:
            t0_, t1_ = (getattr(h, "admit_time", None),
                        getattr(h, "finish_time", None))
            if (h.status == "DONE" and h.tokens and t0_ and t1_
                    and t1_ > t0_):
                tps.append(len(h.tokens) / (t1_ - t0_))
        out["speculative"]["per_request_accept_length"] = _pctl(acc)
        out["speculative"]["tokens_s_per_request"] = _pctl(tps)
        out["speculative"]["pages_claimed"] = getattr(
            engine, "spec_pages_claimed", 0)
        out["speculative"]["pages_rolled_back"] = getattr(
            engine, "spec_pages_rolled_back", 0)
    page_pool = getattr(engine, "page_pool", None)
    if page_pool is not None:
        # occupancy / exhaustion counters in the record (the paged
        # pool's claims/releases/exhausted_events + peak residency)
        out["page_pool"] = page_pool.stats()
        # per-request resident KV bytes — what the admitted-concurrency
        # claims are made of. The MEAN request of this trace, plus the
        # byte budget the whole arena pins, so a quantized-KV record is
        # directly comparable against a bf16 one at equal HBM.
        mean_total = sum(
            p.shape[1] + m for _, p, m, _ in trace
        ) / max(len(trace), 1)
        out["page_pool"]["request_resident_bytes_mean"] = (
            page_pool.request_resident_bytes(int(round(mean_total)))
        )
        out["page_pool"]["token_bytes"] = (
            page_pool.page_bytes() // max(page_pool.page_size, 1)
        )
    if wire is not None:
        out["wire"] = wire
        # HTTP mode runs frontend + engine in-process: their spans are
        # all in the default tracer, no stitching across hosts needed
        from paddle_tpu.observability.tracing import get_tracer

        out["trace"] = trace_report(
            get_tracer().buffer.traces(),
            top_n=args.trace_top, trace_out=args.trace_out,
        )
    return engine, handles, out


def run_kv_compare(args):
    """Replay the SAME paged trace twice — bf16 KV and int8 KV at an
    EQUAL page-arena byte budget — and report residency + concurrency
    side by side. This is the measurable form of the ~2x-slots claim:
    the int8 record must show more usable token-slots (and, under
    backpressure, more peak concurrent requests) for the same HBM."""
    import copy

    base = copy.copy(args)
    base.paged, base.http = True, False

    a_bf16 = copy.copy(base)
    a_bf16.cache_dtype = "bfloat16"
    eng_b, _, rec_b = run_bench(a_bf16)
    arena = eng_b.page_pool.arena_bytes()

    from paddle_tpu.serving import PagedKVPool

    probe = PagedKVPool(
        eng_b.page_pool.config, page_size=args.page_size, num_pages=1,
        dtype="int8", max_seq_len=args.max_seq,
    )
    a_int8 = copy.copy(base)
    a_int8.cache_dtype = "int8"
    # same byte budget: as many int8 pages as fit in the bf16 arena
    # (garbage page included on both sides)
    a_int8.num_pages = max(int(arena // probe.page_bytes()) - 1, 1)
    eng_i, _, rec_i = run_bench(a_int8)

    slots_b = eng_b.page_pool.num_pages * eng_b.page_pool.page_size
    slots_i = eng_i.page_pool.num_pages * eng_i.page_pool.page_size
    return {
        "metric": "serve_kv_compare",
        "equal_hbm_budget_bytes": arena,
        "int8_arena_bytes": eng_i.page_pool.arena_bytes(),
        # compiled-program peak next to the arena budget: the arena is
        # only PART of the resident picture — the per-program estimate
        # covers weights + transients too (full tables nested in the
        # per-dtype records)
        "program_peak_bytes_max": {
            "bfloat16": (rec_b.get("memory") or {}).get("max_peak_bytes"),
            "int8": (rec_i.get("memory") or {}).get("max_peak_bytes"),
        },
        "token_slots": {"bfloat16": slots_b, "int8": slots_i},
        "slots_ratio": round(slots_i / max(slots_b, 1), 3),
        "request_resident_bytes_mean": {
            "bfloat16": rec_b["page_pool"]["request_resident_bytes_mean"],
            "int8": rec_i["page_pool"]["request_resident_bytes_mean"],
        },
        "peak_active_requests": {
            "bfloat16": rec_b["peak_active_requests"],
            "int8": rec_i["peak_active_requests"],
        },
        "peak_pages_in_use": {
            "bfloat16": rec_b["page_pool"]["peak_pages_in_use"],
            "int8": rec_i["page_pool"]["peak_pages_in_use"],
        },
        "bfloat16": rec_b,
        "int8": rec_i,
    }


def run_shared_prefix(args):
    """Shared-prefix scenario: Poisson replay where every prompt opens
    with ONE common system prefix (``--prefix-len`` tokens) followed by
    a short unique tail — the millions-of-users shape. The SAME trace
    replays twice: COLD (prefix cache off — every request re-prefills
    the prefix and claims private pages) and WARM (prefix cache on,
    seeded by one publisher request off the clock). The record carries
    warm-vs-cold TTFT percentiles and the p50 collapse ratio, the
    hit/eviction/COW counters, and the peak shared-page HBM savings —
    the measurable form of the near-zero-prefill + near-zero-marginal-
    HBM claim."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import PagedServingEngine

    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=2 * args.hidden, num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()

    rng = np.random.RandomState(args.seed)
    prefix = rng.randint(0, args.vocab, (args.prefix_len,))
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(args.requests):
        t = int(rng.randint(1, args.tail_max + 1))
        ids = np.concatenate(
            [prefix, rng.randint(0, args.vocab, (t,))]
        )[None, :]
        m = int(rng.randint(args.new_min, args.new_max + 1))
        trace.append((float(arrivals[i]), ids, m))

    def build(prefix_cache):
        # demand paging ON for BOTH engines: the ratio must isolate the
        # prefix cache, not conflate it with the admission-claim change
        return PagedServingEngine(
            net, max_batch_size=args.max_batch,
            max_seq_len=args.max_seq, cache_dtype=args.cache_dtype,
            min_bucket=args.min_bucket, max_queue_size=args.max_queue,
            page_size=args.page_size, num_pages=args.num_pages,
            prefix_cache=prefix_cache, demand_paging=True,
        )

    def replay(engine, sample_saved=None):
        t0 = time.monotonic()
        pending = list(trace)
        handles = []
        while pending or engine.scheduler.depth or engine.active_slots:
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                _, ids, m = pending.pop(0)
                handles.append(engine.submit(ids, m))
            if engine.scheduler.depth or engine.active_slots:
                engine.step()
                if sample_saved is not None:
                    sample_saved()
            elif pending:
                time.sleep(min(0.001, pending[0][0] - now))
        return handles, time.monotonic() - t0

    def warm_compiles(engine):
        # the fixed-shape inventory (also fills the per-program
        # peak-bytes table), then the publisher request — which
        # doubles as the cache seed
        engine.warmup()
        h = engine.submit(trace[0][1], 2)
        engine.run_until_idle()
        assert h.status == "DONE", (h.status, h.reason)
        if engine.prefix_cache is not None:
            h = engine.submit(trace[1][1], 2)  # first WARM hit compiles
            engine.run_until_idle()
            assert h.status == "DONE", (h.status, h.reason)
        engine.metrics = type(engine.metrics)()

    # ---- cold: no sharing, full prefill per request
    cold = build(None)
    warm_compiles(cold)
    cold_handles, cold_wall = replay(cold)
    cold_rep = cold.metrics.report()
    cold_mem = cold.memory_report()
    cold.close()

    # ---- warm: publisher seeds the prefix, every replay request hits
    warm = build(True)
    warm_compiles(warm)
    saved_peak = [0]

    def sample_saved():
        saved_peak[0] = max(saved_peak[0],
                            warm.prefix_cache.hbm_saved_bytes())

    warm_handles, warm_wall = replay(warm, sample_saved)
    warm_rep = warm.metrics.report()
    pstats = warm.prefix_cache.stats()
    pool_stats = warm.page_pool.stats()
    warm_mem = warm.memory_report()
    warm.close()

    def pct(rep):
        s = rep["ttft"]
        return {k: s.get(k) for k in ("count", "p50", "p90", "p99",
                                      "max")}

    cold_p50 = cold_rep["ttft"]["p50"] or 0.0
    warm_p50 = warm_rep["ttft"]["p50"] or 0.0
    return {
        "metric": "serve_shared_prefix",
        "requests": args.requests,
        "rate_req_s": args.rate,
        "prefix_len": args.prefix_len,
        "tail_max": args.tail_max,
        "cache_dtype": str(warm.cache_dtype),
        "page_size": args.page_size,
        "cold": {
            "wall_s": round(cold_wall, 3),
            "completed": sum(1 for h in cold_handles
                             if h.status == "DONE"),
            "ttft": pct(cold_rep),
        },
        "warm": {
            "wall_s": round(warm_wall, 3),
            "completed": sum(1 for h in warm_handles
                             if h.status == "DONE"),
            "ttft": pct(warm_rep),
        },
        "ttft_p50_ratio": (round(cold_p50 / warm_p50, 2)
                           if warm_p50 else None),
        "prefix_cache": pstats,
        "page_pool": pool_stats,
        "hbm_saved_bytes_peak": saved_peak[0],
        # per-program peak-bytes next to the page-arena numbers; warm
        # carries the gather/chunk warm-path programs cold never
        # compiles
        "memory": {
            "cold": cold_mem,
            "warm": warm_mem,
        },
    }


def run_multi_turn(args):
    """Multi-turn conversation scenario: ``--sessions`` independent
    chats, each ``--turns`` turns deep, served through the session KV
    runtime (prefix cache + decode-publish + tiered spill + session
    store). Turn N+1's prompt is the FULL turn-N conversation —
    prompt AND generated answer — plus a fresh user tail, so a warm
    turn re-prefills only the tail. The record carries per-turn-index
    TTFT percentiles and the turn-2-vs-warm-prefix ratio (turn 2 must
    cost about what a plain warm-prefix hit costs: the decode-written
    answer KV is as reusable as prefill KV). A bookkeeping-only
    capacity sweep then force-spills every refcount-0 page and counts
    how many FULL conversations stay servable from the sub-HBM tiers
    at several simulated host budgets — resident conversational state
    scaling with host RAM at fixed HBM."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import PagedServingEngine

    turns = int(args.turns)
    longest = args.prompt_max + turns * (args.tail_max + args.new_max)
    if longest > args.max_seq:
        raise SystemExit(
            f"--multi-turn: worst-case conversation {longest} tokens "
            f"exceeds --max-seq {args.max_seq}; lower --turns/--new-max "
            f"or raise --max-seq"
        )

    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=2 * args.hidden, num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()

    rng = np.random.RandomState(args.seed)
    host_budget = int(args.spill_host_mb) << 20
    eng = PagedServingEngine(
        net, max_batch_size=args.max_batch, max_seq_len=args.max_seq,
        cache_dtype=args.cache_dtype, min_bucket=args.min_bucket,
        max_queue_size=args.max_queue, page_size=args.page_size,
        num_pages=args.num_pages, prefix_cache=True,
        kv_tiering={"host_budget_bytes": host_budget},
        sessions=True, demand_paging=True,
    )

    def timed_turn(ids, max_new, session_id):
        t0 = time.monotonic()
        first = [None]

        def on_token(tok, handle):
            if first[0] is None:
                first[0] = time.monotonic() - t0

        h = eng.submit(np.asarray([list(ids)]), max_new,
                       session_id=session_id, on_token=on_token)
        eng.run_until_idle()
        assert h.status == "DONE", (h.status, h.reason)
        return h, first[0]

    # throwaway conversation compiles every program shape off the
    # clock: prefill buckets, decode step, and the warm-hit
    # gather/adopt path a turn-2 submit exercises
    eng.warmup()
    wc = [int(t) for t in rng.randint(0, args.vocab,
                                      (args.prompt_min + 8,))]
    h, _ = timed_turn(wc, 4, "warmup-chat")
    timed_turn(list(wc) + [int(t) for t in h.tokens] + [1, 2, 3], 4,
               "warmup-chat")
    eng.metrics = type(eng.metrics)()

    n_sessions = int(args.sessions)
    convs = [
        [int(t) for t in rng.randint(
            0, args.vocab,
            (int(rng.randint(args.prompt_min, args.prompt_max + 1)),))]
        for _ in range(n_sessions)
    ]
    ttft_by_turn = [[] for _ in range(turns)]
    ref_specs = []
    ref_ttfts = []
    for t in range(turns):
        for s in range(n_sessions):
            m = int(rng.randint(args.new_min, args.new_max + 1))
            if t > 0:
                tail = [int(x) for x in rng.randint(
                    0, args.vocab,
                    (int(rng.randint(1, args.tail_max + 1)),))]
                if t == 1:
                    ref_specs.append((list(convs[s]), len(tail), m))
                convs[s] = convs[s] + tail
            h, ttft = timed_turn(convs[s], m, f"chat-{s}")
            ttft_by_turn[t].append(ttft)
            convs[s] = convs[s] + [int(x) for x in h.tokens]
            if t == 1:
                # warm-prefix reference, interleaved submit-for-submit
                # with the turn-2 requests it is compared against (so
                # drifting host load cancels out of the ratio): the
                # turn-1 conversation again with a FRESH same-length
                # tail and no session identity — hits exactly the
                # pages turn 2 hit and chunk-prefills the same tail
                # work, so the ratio isolates what the session path
                # ADDS (store touch, restore probes) over a plain
                # warm-prefix request. Re-submitting the literal
                # turn-2 prompt would be unfair the other way: its
                # own published answer covers the whole prompt, zero
                # prefill.
                base, tail_len, mr = ref_specs[-1]
                ids = base + [int(x) for x in rng.randint(
                    0, args.vocab, (tail_len,))]
                _, rttft = timed_turn(ids, mr, None)
                ref_ttfts.append(rttft)

    pc = eng.prefix_cache
    tier = eng.kv_tier
    t2 = _pctl(ttft_by_turn[1] if turns > 1 else [])
    ref = _pctl(ref_ttfts)
    ratio = (round(t2["p50"] / ref["p50"], 3)
             if t2.get("p50") and ref.get("p50") else None)

    # ---- capacity sweep: force-spill everything refcount-0, then a
    # bookkeeping-only walk (no restores, no decompression) over each
    # conversation's chain keys. Simulated budgets keep the NEWEST
    # spill records that fit (the store's own LRU policy) — resident
    # full conversations must grow with the sub-HBM byte budget.
    forced = pc.evict(10 ** 9)
    wv = eng.weights_version
    ps = eng.page_pool.page_size
    root = pc.root_key(wv)

    def chain_keys(ids):
        # the LAST emitted token's KV is never written (decode stops
        # after sampling it), so the publishable span is len-1 — a
        # final page that would need that token can never be resident
        keys, key = [], root
        for i in range(0, ((len(ids) - 1) // ps) * ps, ps):
            key = (key, tuple(int(x) for x in ids[i:i + ps]))
            keys.append(key)
        return keys

    keys_per_session = [chain_keys(conv) for conv in convs]
    recs = tier.iter_records()  # coldest first

    def resident_sessions(budget):
        kept, used = set(), 0
        for rec in reversed(recs):  # newest first, LRU keep
            if used + rec.nbytes > budget:
                break
            used += rec.nbytes
            kept.add(rec.key)
        return sum(
            1 for keys in keys_per_session
            if keys and all(k in kept or pc.peek(k) is not None
                            for k in keys)
        )

    # budgets are fractions of what actually spilled (the configured
    # budget may dwarf a smoke-sized workload): the growth curve is
    # the claim, resident conversations rising with sub-HBM bytes
    spilled_bytes = sum(r.nbytes for r in recs)
    sweep = [
        {"simulated_budget_bytes": b,
         "resident_sessions": resident_sessions(b)}
        for b in sorted({max(1, spilled_bytes // 8),
                         max(1, spilled_bytes // 4),
                         max(1, spilled_bytes // 2), spilled_bytes})
    ]
    actual = sum(
        1 for keys in keys_per_session
        if keys and all(pc.peek(k) is not None
                        or tier.peek(k) is not None for k in keys)
    )
    cap_block = {
        "spilled_bytes": spilled_bytes,
        "resident_sessions_after_full_spill": actual,
        "sweep": sweep,
    }

    sess_stats = eng.sessions.stats()
    tstats = tier.stats()
    pstats = pc.stats()
    pool_stats = eng.page_pool.stats()
    eng.close()
    return {
        "metric": "serve_multi_turn",
        "sessions": n_sessions,
        "turns": turns,
        "page_size": args.page_size,
        "cache_dtype": str(eng.cache_dtype),
        "spill_host_budget_bytes": host_budget,
        "ttft_by_turn": [_pctl(xs) for xs in ttft_by_turn],
        "warm_prefix_ttft": ref,
        "turn2_vs_warm_prefix_ttft_ratio": ratio,
        "forced_spill_pages": forced,
        "capacity": cap_block,
        "session_store": sess_stats,
        "kv_tier": tstats,
        "prefix_cache": pstats,
        "page_pool": pool_stats,
    }


def run_fleet_bench(args):
    """Fleet mode: spawn ``--fleet N`` replica SUBPROCESSES on
    ephemeral ports (identical weights via the shared seed), put the
    occupancy-aware router in front, and replay the Poisson trace
    through it — every request a real POST + SSE stream. The record
    carries aggregate throughput next to PER-REPLICA occupancy
    (sampled active rows + the page pool's own lifetime peak), which
    is what the 1->2 replica ~linear-scaling claim is made of.
    ``--fleet-prefill`` additionally spawns a prefill-pool worker and
    attaches every replica to it (cross-process disaggregation)."""
    import threading

    from paddle_tpu.serving import HTTPRejected, stream_generate
    from paddle_tpu.serving.fleet import FleetRouter
    from paddle_tpu.serving.fleet.launch import spawn, spawn_all

    n = int(args.fleet)
    common = [
        "--vocab", args.vocab, "--hidden", args.hidden,
        "--layers", args.layers, "--heads", args.heads,
        "--seed", args.seed, "--max-batch", args.max_batch,
        "--max-seq", args.max_seq, "--min-bucket", args.min_bucket,
        "--page-size", args.page_size, "--max-queue", args.max_queue,
        "--cache-dtype", args.cache_dtype,
    ]
    if args.num_pages is not None:
        common += ["--num-pages", args.num_pages]
    if not args.warmup:
        common += ["--no-warmup"]
    procs, worker, router = [], None, None
    try:
        if args.fleet_prefill:
            worker = spawn("prefill", common)
            common += ["--prefill-worker", f"127.0.0.1:{worker.port}"]
        print(f"serve_bench: spawning {n} replica(s)...",
              file=sys.stderr)
        procs = spawn_all([("replica", common)] * n)
        router = FleetRouter(
            [("127.0.0.1", p.port) for p in procs],
            health_interval_s=0.05,
        ).start()
        trace = build_trace(
            args.requests, args.rate, args.seed, args.vocab,
            args.prompt_min, args.prompt_max, args.new_min,
            args.new_max,
        )
        results = [None] * len(trace)
        ttfts, itls, rejects, tokens = [], [], {}, [0]
        lock = threading.Lock()

        def one(i, ids, max_new, cls):
            try:
                events, tm = stream_generate(
                    "127.0.0.1", router.port,
                    {"input_ids": [int(t) for t in ids[0]],
                     "max_new_tokens": int(max_new),
                     "slo_class": cls},
                )
            except HTTPRejected as e:
                with lock:
                    reason = (e.body or {}).get("reason",
                                                f"http_{e.code}")
                    rejects[reason] = rejects.get(reason, 0) + 1
                    results[i] = _HTTPHandle("REJECTED", reason)
                return
            toks = [d["token"] for ev, d in events if ev == "token"]
            last = events[-1] if events else ("error", {})
            status = (last[1] or {}).get("status", "ERROR") \
                if last[0] == "done" else "ERROR"
            with lock:
                results[i] = _HTTPHandle(
                    status, (last[1] or {}).get("reason"), toks)
                tokens[0] += len(toks)
                if tm["ttft_s"] is not None:
                    ttfts.append(tm["ttft_s"])
                itls.extend(tm["itl_s"])

        peak_active = [0] * n
        done_flag = threading.Event()

        def sample_peaks():
            while not done_flag.is_set():
                for i, r in enumerate(router.replicas):
                    st = r.status or {}
                    peak_active[i] = max(peak_active[i],
                                         int(st.get("active") or 0))
                time.sleep(0.01)

        sampler = threading.Thread(target=sample_peaks, daemon=True)
        sampler.start()
        t0 = time.monotonic()
        threads = []
        try:
            for i, (arrival, ids, max_new, cls) in enumerate(trace):
                dt = arrival - (time.monotonic() - t0)
                if dt > 0:
                    time.sleep(dt)
                th = threading.Thread(target=one,
                                      args=(i, ids, max_new, cls),
                                      daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=600)
            wall = time.monotonic() - t0
        finally:
            done_flag.set()
            sampler.join(timeout=5)
        per_replica = []
        routed = router.metrics.requests.by_label()
        for i, p in enumerate(procs):
            st = (router.replicas[i].status or {})
            per_replica.append({
                "port": p.port,
                "requests_routed": int(routed.get(str(i), 0)),
                "peak_active_sampled": peak_active[i],
                "free_pages": st.get("free_pages"),
                "page_pool": st.get("page_pool"),
                "remote_prefill": st.get("remote_prefill"),
            })
        done = sum(1 for r in results
                   if r is not None and r.status == "DONE")
        out = {
            "metric": "serve_fleet_bench",
            "mode": "fleet",
            "replicas": n,
            "prefill_pool": bool(args.fleet_prefill),
            "requests": args.requests,
            "rate_req_s": args.rate,
            "wall_s": round(wall, 3),
            "completed": done,
            "tokens_out": tokens[0],
            "decode_tok_s": round(tokens[0] / wall, 1),
            "req_s": round(done / wall, 2),
            "rejected_by_reason": rejects,
            "per_replica": per_replica,
            "router": {
                "retries": router.metrics.retries.by_label(),
                "shed": router.metrics.shed.by_label(),
                "breaker_opens":
                    router.metrics.breaker_opens.by_label(),
                "stream_aborts":
                    router.metrics.stream_aborts.by_label(),
            },
            "wire": {"ttft": _pctl(ttfts), "itl": _pctl(itls)},
        }
        # stitched distributed traces: the router's own tracer plus
        # every replica's /trace endpoint (replica buffers already
        # carry the KV-client and prefill-worker spans)
        groups = list(router.tracer.buffer.traces())
        for p in procs:
            groups.extend(_fetch_remote_traces("127.0.0.1", p.port))
        out["trace"] = trace_report(
            groups, top_n=args.trace_top, trace_out=args.trace_out,
        )
        return out
    finally:
        if router is not None:
            router.stop()
        for p in procs:
            p.terminate()
        if worker is not None:
            worker.terminate()


class _HTTPHandle:
    """Duck-typed result row for the HTTP replay (matches the `.status`
    surface the report counts)."""

    def __init__(self, status, reason=None, tokens=()):
        self.status = status
        self.reason = reason
        self.tokens = list(tokens)


def _pctl(xs):
    import numpy as np

    if not xs:
        return {"count": 0}
    a = np.asarray(xs, float)
    return {
        "count": int(a.size),
        "mean": float(a.mean()),
        "p50": float(np.percentile(a, 50)),
        "p90": float(np.percentile(a, 90)),
        "p99": float(np.percentile(a, 99)),
        "max": float(a.max()),
    }


def _fetch_remote_traces(host, port, timeout=10.0):
    """GET /trace from one fleet process; [] on any failure — trace
    collection must never fail a bench run."""
    import http.client

    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        conn.request("GET", "/trace")
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        if resp.status != 200:
            return []
        return json.loads(body).get("traces", [])
    except Exception:
        return []


def trace_report(span_groups, top_n=8, trace_out=None):
    """Stitch every collected trace onto one clock, report the per-hop
    latency breakdown (p50/p99 per span name across all requests), and
    optionally record the ``top_n`` SLOWEST requests' full stitched
    traces to ``trace_out`` — the requests worth staring at."""
    from paddle_tpu.observability.tracing import stitch

    by_trace = {}
    for s in stitch(span_groups):
        if s.get("end") is None:
            continue
        by_trace.setdefault(s["trace_id"], []).append(s)
    durs, roots = {}, []
    for tid, spans in by_trace.items():
        for s in spans:
            durs.setdefault(s["name"], []).append(
                float(s["end"]) - float(s["start"])
            )
        root = next((s for s in spans if not s.get("parent_id")), None)
        if root is not None:
            roots.append(
                (float(root["end"]) - float(root["start"]), tid)
            )
    report = {
        "traces": len(by_trace),
        "hops": {name: _pctl(v) for name, v in sorted(durs.items())},
    }
    if trace_out:
        roots.sort(reverse=True)
        slow = [
            {"trace_id": tid, "duration_s": round(d, 6),
             "spans": sorted(by_trace[tid],
                             key=lambda s: float(s["start"]))}
            for d, tid in roots[:top_n]
        ]
        with open(trace_out, "w") as f:
            json.dump({"slowest": slow}, f, indent=2, default=str)
        report["trace_out"] = trace_out
        report["recorded"] = len(slow)
    return report


def run_http_trace(engine, trace):
    """Replay the trace through the HTTP/SSE front-end on localhost —
    one thread per request, arrivals honored, every token crossing a
    real socket. Returns (handles, wall_s, wire-stats dict,
    peak-concurrency sample)."""
    import threading

    from paddle_tpu.serving import (
        HTTPRejected,
        ServingFrontend,
        stream_generate,
    )

    fe = ServingFrontend(engine).start()
    results = [None] * len(trace)
    ttfts, itls, rejects = [], [], {}
    lock = threading.Lock()

    def one(i, ids, max_new, cls):
        try:
            events, tm = stream_generate(
                "127.0.0.1", fe.port,
                {"input_ids": [int(t) for t in ids[0]],
                 "max_new_tokens": int(max_new),
                 "slo_class": cls},
            )
        except HTTPRejected as e:
            with lock:
                reason = (e.body or {}).get("reason", f"http_{e.code}")
                rejects[reason] = rejects.get(reason, 0) + 1
                results[i] = _HTTPHandle("REJECTED", reason)
            return
        toks = [d["token"] for ev, d in events if ev == "token"]
        last = events[-1] if events else ("error", {})
        status = (last[1] or {}).get("status", "ERROR") \
            if last[0] in ("done", "error") else "ERROR"
        with lock:
            results[i] = _HTTPHandle(status, (last[1] or {}).get(
                "reason"), toks)
            if tm["ttft_s"] is not None:
                ttfts.append(tm["ttft_s"])
            itls.extend(tm["itl_s"])

    t0 = time.monotonic()
    threads = []
    peak = [0]
    done = threading.Event()

    def sample_peak():
        # the frontend's driver thread steps the engine; sample its
        # concurrency here so wire-mode records carry the same
        # peak_active_requests the in-process replay reports
        while not done.is_set():
            peak[0] = max(peak[0], engine.active_slots)
            time.sleep(0.005)

    sampler = threading.Thread(target=sample_peak, daemon=True)
    sampler.start()
    try:
        for i, (arrival, ids, max_new, cls) in enumerate(trace):
            dt = arrival - (time.monotonic() - t0)
            if dt > 0:
                time.sleep(dt)
            th = threading.Thread(target=one,
                                  args=(i, ids, max_new, cls),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall = time.monotonic() - t0
    finally:
        done.set()
        sampler.join(timeout=5)
        fe.stop()
    wire = {
        "ttft": _pctl(ttfts),
        "itl": _pctl(itls),
        "rejected_by_reason": rejects,
        "stream_aborts": fe.metrics.stream_aborts.by_label(),
    }
    return ([r or _HTTPHandle("ERROR") for r in results], wall, wire,
            peak[0])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--min-bucket", type=int, default=8)
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=24)
    ap.add_argument("--new-min", type=int, default=4)
    ap.add_argument("--new-max", type=int, default=24)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="serve through PagedServingEngine (page-pool "
                         "KV residency) instead of the decode slab")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size in tokens (paged engine)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="usable page count (default: full coverage)")
    ap.add_argument("--demand-paging", action="store_true",
                    default=None,
                    help="paged engine: claim only prompt pages at "
                         "admission and grow decode (and speculative "
                         "verify) pages on demand")
    ap.add_argument("--http", action="store_true",
                    help="replay through the HTTP/SSE front-end over "
                         "localhost; records wire-level TTFT/ITL next "
                         "to the in-process numbers")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="spawn N replica subprocesses on ephemeral "
                         "ports and replay the trace through the "
                         "occupancy-aware FleetRouter; records "
                         "per-replica occupancy + aggregate throughput")
    ap.add_argument("--fleet-prefill", action="store_true",
                    help="with --fleet: also spawn a prefill-pool "
                         "worker and attach every replica to it "
                         "(cross-process prefill/decode "
                         "disaggregation)")
    ap.add_argument("--kv-compare", action="store_true",
                    help="run the paged trace twice — bf16 KV vs int8 "
                         "KV at an EQUAL page-arena byte budget — and "
                         "report residency/concurrency side by side")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix scenario: Poisson replay over "
                         "one common system prompt, run COLD (no "
                         "prefix cache) then WARM (cache seeded); "
                         "records warm-vs-cold TTFT percentiles, "
                         "hit/evict counters and shared-page HBM "
                         "savings")
    ap.add_argument("--prefix-len", type=int, default=96,
                    help="shared system-prefix length in tokens "
                         "(--shared-prefix)")
    ap.add_argument("--tail-max", type=int, default=8,
                    help="max unique per-request tail tokens after the "
                         "shared prefix (--shared-prefix / --multi-turn)")
    ap.add_argument("--multi-turn", action="store_true",
                    help="multi-turn conversation scenario through the "
                         "session KV runtime: --sessions chats x "
                         "--turns turns, each turn's prompt = the full "
                         "prior conversation + a fresh tail; records "
                         "per-turn TTFT percentiles, the turn-2-vs-"
                         "warm-prefix ratio, and a spill-capacity sweep")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per conversation (--multi-turn)")
    ap.add_argument("--sessions", type=int, default=8,
                    help="concurrent conversations (--multi-turn)")
    ap.add_argument("--spill-host-mb", type=int, default=64,
                    help="host-RAM budget in MiB for the KV spill tier "
                         "(--multi-turn)")
    ap.add_argument("--speculate", nargs="+", default=None,
                    metavar="KEY=VAL",
                    help="speculative decoding: 'draft=self:<N>' "
                         "(early-exit draft after N target layers, no "
                         "extra weights) or 'draft=tiny:<L>' (fresh "
                         "L-layer half-width draft), plus 'k=<K>' "
                         "proposal length — e.g. "
                         "--speculate draft=self:1 k=7; the record "
                         "gains per-request acceptance length and "
                         "tokens/s/request")
    ap.add_argument("--zero-from-layer", type=int, default=None,
                    metavar="N",
                    help="zero o_proj/down_proj of every layer >= N so "
                         "those layers are exact identities — makes "
                         "draft=self:N bitwise-equal to the target "
                         "(full acceptance), the spec-smoke "
                         "upper-bound shape")
    ap.add_argument("--no-warmup", dest="warmup", action="store_false")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the --trace-top SLOWEST requests' "
                         "stitched distributed traces to PATH (JSON); "
                         "the bench report always carries the per-hop "
                         "p50/p99 breakdown in http/fleet modes")
    ap.add_argument("--trace-top", type=int, default=8,
                    help="how many slowest-request traces --trace-out "
                         "records")
    ap.add_argument("--mix", default=None, metavar="NAMES",
                    help="comma list of traffic scenarios "
                         "(chat,rag,batch,agent) replacing the uniform "
                         "Poisson trace — each scenario has its own "
                         "arrival shape and SLO class; the record "
                         "gains a per-class 'slo' attainment block")
    ap.add_argument("--json", action="store_true",
                    help="print the JSON report only")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="also dump the final process metrics registry "
                         "in Prometheus text format to PATH")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics over HTTP on this port for the "
                         "duration of the bench (0 = ephemeral port)")
    args = ap.parse_args(argv)

    server = None
    if args.metrics_port is not None:
        from paddle_tpu.observability import start_metrics_server

        server = start_metrics_server(port=args.metrics_port)
        print(f"serve_bench: metrics at {server.url}", file=sys.stderr)
    try:
        if args.fleet:
            out = run_fleet_bench(args)
            if args.json:
                print(json.dumps(out, indent=2, default=str))
            else:
                per = ", ".join(
                    f"r{i}: {p['requests_routed']} reqs peak "
                    f"{p['peak_active_sampled']}"
                    for i, p in enumerate(out["per_replica"])
                )
                print(
                    f"serve_bench --fleet {out['replicas']}: "
                    f"{out['completed']}/{out['requests']} done in "
                    f"{out['wall_s']}s — {out['decode_tok_s']} "
                    f"decode tok/s aggregate ({per}); router "
                    f"retries={out['router']['retries']} "
                    f"shed={out['router']['shed']}"
                )
            return out
        if args.shared_prefix:
            out = run_shared_prefix(args)
            if args.json:
                print(json.dumps(out, indent=2, default=str))
            else:
                c, w = out["cold"]["ttft"], out["warm"]["ttft"]
                pc = out["prefix_cache"]
                print(
                    f"shared-prefix ({out['prefix_len']} tokens): TTFT "
                    f"p50 cold={1e3 * (c['p50'] or 0):.2f}ms warm="
                    f"{1e3 * (w['p50'] or 0):.2f}ms "
                    f"(x{out['ttft_p50_ratio']}), hits={pc['hits']} "
                    f"misses={pc['misses']} evictions={pc['evictions']} "
                    f"cow={pc['cow_clones']}, shared-HBM peak "
                    f"{out['hbm_saved_bytes_peak']} B"
                )
            return out
        if args.multi_turn:
            out = run_multi_turn(args)
            if args.json:
                print(json.dumps(out, indent=2, default=str))
            else:
                t1 = out["ttft_by_turn"][0]
                t2 = (out["ttft_by_turn"][1]
                      if len(out["ttft_by_turn"]) > 1 else {})
                cap = out["capacity"]
                sweep = ", ".join(
                    f"{c['simulated_budget_bytes'] >> 10}KiB->"
                    f"{c['resident_sessions']}"
                    for c in cap["sweep"]
                )
                print(
                    f"multi-turn ({out['sessions']} chats x "
                    f"{out['turns']} turns): TTFT p50 turn1="
                    f"{1e3 * (t1.get('p50') or 0):.2f}ms turn2="
                    f"{1e3 * (t2.get('p50') or 0):.2f}ms, turn2/warm-"
                    f"prefix x{out['turn2_vs_warm_prefix_ttft_ratio']}; "
                    f"forced spill {out['forced_spill_pages']} pages, "
                    f"{cap['resident_sessions_after_full_spill']}/"
                    f"{out['sessions']} conversations fully tier-"
                    f"resident (sweep: {sweep})"
                )
            return out
        if args.kv_compare:
            out = run_kv_compare(args)
            if args.json:
                print(json.dumps(out, indent=2, default=str))
            else:
                print(
                    f"kv-compare at {out['equal_hbm_budget_bytes']} "
                    f"arena bytes: token-slots bf16="
                    f"{out['token_slots']['bfloat16']} int8="
                    f"{out['token_slots']['int8']} "
                    f"(x{out['slots_ratio']}), peak concurrent "
                    f"bf16={out['peak_active_requests']['bfloat16']} "
                    f"int8={out['peak_active_requests']['int8']}"
                )
            return out
        engine, handles, out = run_bench(args)
    finally:
        if server is not None:
            server.stop()
    if args.prom_out:
        from paddle_tpu.observability import prometheus_text

        with open(args.prom_out, "w") as f:
            f.write(prometheus_text())
        print(f"serve_bench: prometheus exposition -> {args.prom_out}",
              file=sys.stderr)
    if args.json:
        print(json.dumps(out, indent=2, default=str))
    else:
        print(
            f"serve_bench: {out['completed']}/{out['requests']} done in "
            f"{out['wall_s']}s — {out['decode_tok_s']} decode tok/s, "
            f"{out['req_s']} req/s, {out['rejected']} rejected, "
            f"{out['timeouts']} timeouts, steps={out['engine_steps']}"
        )
        sp = out.get("speculative")
        if sp:
            tr = sp["tokens_s_per_request"]
            print(
                f"speculative ({sp['mode']} k={sp['k']}): "
                f"mean accept length {sp['mean_accept_length']} over "
                f"{sp['rounds']} rounds "
                f"({sp['accepted']}/{sp['proposed']} proposed tokens "
                f"accepted), tokens/s/request p50="
                f"{tr.get('p50', 0.0):.1f}"
            )
        for cls, entry in sorted((out.get("slo") or {}).items()):
            parts = []
            for metric in ("ttft", "itl", "e2e"):
                e = entry.get(metric)
                if e:
                    parts.append(
                        f"{metric} {100 * e['attainment']:.1f}% "
                        f"(budget {e['budget_s']}s, "
                        f"{e['breaches']} breach)"
                    )
            print(f"slo[{cls}] target {100 * entry['target']:.0f}%: "
                  + "; ".join(parts))
        print(engine.metrics.render())
    return out


if __name__ == "__main__":
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from paddle_tpu.jit import place_compile_cache

    place_compile_cache()
    main()
