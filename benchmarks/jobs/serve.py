"""Job ``serve``: ``PagedServingEngine`` behind ``ServingFrontend``
over loopback HTTP with SSE, the path users call. The load generator
runs in this process (it holds the chip), one thread a stream.

Set-up: build, warm the prompt buckets this cell's lengths reach, pass
the correctness check (fixed lengths, seeded tokens, through the same
front end), start the traffic. Closed loop: the window opens once every
client is streaming. Open loop: after ``ramp_s`` of the same traffic.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks import flops, harness, loadgen


def _check(ctx, net, cfg, port, spec):
    """Seeded requests of fixed lengths through the front end; every
    served token's reference logit against the top one."""
    rng = np.random.default_rng(ctx.seed + 1)
    reqs = [loadgen.Request(i, None, rng.integers(0, cfg["vocab_size"], n),
                            spec["max_new"])
            for i, n in enumerate(spec["prompt_lens"])]
    threads = [threading.Thread(target=loadgen.stream, args=(port, r))
               for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    weights = ctx.builder.weights(net)
    ok, worst, means = True, 0.0, []
    for r in reqs:
        if r.status != "DONE" or len(r.tokens) != r.max_new:
            harness.line("check_failed", request=r.index, status=r.status,
                         tokens=len(r.tokens))
            return False
        gaps = ctx.reference.served_token_gaps(
            weights, cfg, r.prompt, r.tokens, int(spec["pad_to"]))
        worst = max(worst, float(gaps.max()))
        means.append(float(gaps.mean()))
        ok = ok and bool(np.isfinite(gaps).all())
    mean = float(np.mean(means))
    ok = ok and worst <= ctx.reference.SERVE_LOGIT_GAP \
        and mean <= ctx.reference.SERVE_MEAN_GAP
    harness.line("check", requests=len(reqs), tokens_each=spec["max_new"],
                 max_logit_gap=worst, allowed=ctx.reference.SERVE_LOGIT_GAP,
                 mean_logit_gap=mean, allowed_mean=ctx.reference.SERVE_MEAN_GAP,
                 ok=ok)
    return ok


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedServingEngine, ServingFrontend

    cfg, cell, mix = ctx.config, ctx.cell, ctx.mix
    eng = dict(cell["engine"])
    paddle.seed(ctx.seed)
    harness.note("serve: building the net")
    net, _ = ctx.builder.build(cfg, ctx.seed, cell.get("param_dtype",
                                                       "bfloat16"))
    net.eval()
    engine = PagedServingEngine(net, **eng)
    if mix["kind"] == "closed_loop":
        per_client = loadgen.plan_closed_loop(mix, ctx.seed, cfg["vocab_size"])
        planned = [r for reqs in per_client for r in reqs]
    else:
        planned = loadgen.plan_open_loop(mix, ctx.seed, cfg["vocab_size"],
                                         ctx.seconds)
    plens = [len(r.prompt) for r in planned]
    harness.line("traffic", mix=mix["kind"], requests=len(planned),
                 prompt_len=loadgen.describe(plens),
                 output_len=loadgen.describe([r.max_new for r in planned]))
    buckets = sorted({engine.pool.bucket_for(n)
                      for n in plens + list(cell["check"]["prompt_lens"])})
    harness.note(f"serve: warming buckets {buckets}")
    warm = engine.warmup(buckets=buckets)
    fe = ServingFrontend(engine).start()
    loop = None
    try:
        harness.note("serve: correctness check")
        ok = _check(ctx, net, cfg, fe.port, cell["check"])
        harness.note("serve: starting the traffic")
        if mix["kind"] == "closed_loop":
            loop = loadgen.ClosedLoop(fe.port, per_client).start()
            t0 = None
            deadline = time.perf_counter() + float(mix.get("fill_timeout_s", 120))
            while not loop.all_streaming():
                if time.perf_counter() > deadline:
                    raise RuntimeError("the slots never all filled")
                time.sleep(0.02)
            w0 = ctx.window_opens()
        else:
            loop = loadgen.OpenLoop(fe.port, planned,
                                    workers=int(mix.get("streams", 128))).start()
            t0 = loop.t0
            time.sleep(max(0.0, t0 + float(mix.get("ramp_s", 0))
                           - time.perf_counter()))
            w0 = ctx.window_opens()
        w1 = w0 + float(ctx.seconds)
        compiles0 = ctx.compiles.compiles
        rep0 = engine.metrics.report()
        tw, resident = None, None
        if ctx.trace:
            time.sleep(max(0.0, w0 + ctx.seconds / 3.0 - time.perf_counter()))
            tw = harness.TraceWindow(ctx.root, ctx.name)
            tw.start()
            time.sleep(float(cell.get("trace_seconds", 3)))
            tw.stop()
            reqs = loop.requests()
            resident = 0.5 * (loadgen.resident_tokens(reqs, tw.t0)
                              + loadgen.resident_tokens(reqs, tw.t1))
        time.sleep(max(0.0, w1 - time.perf_counter()))
        reqs = loop.requests() if t0 is None else planned
        m = loadgen.window_measures(reqs, w0, w1, t0)
        m["backlog_mid"] = loadgen.backlog(reqs, 0.5 * (w0 + w1), t0)
        m["backlog_end"] = loadgen.backlog(reqs, w1, t0)
        rep1 = engine.metrics.report()
        compiled_in_window = ctx.compiles.compiles - compiles0
    finally:
        if loop is not None:
            loop.stop.set()
        fe.stop(close_engine=True)
        if loop is not None and not loop.join(30):
            harness.note("serve: a client thread did not end")
    e2e = {"serve_tok_s": m["tokens"] / float(ctx.seconds)}
    if m["gaps"]:
        e2e["itl_p95_ms"] = 1e3 * loadgen.percentile(m["gaps"], 95)
    if m["ttft"] and mix["kind"] == "open_loop":
        e2e["ttft_p95_ms"] = 1e3 * loadgen.percentile(m["ttft"], 95)
    ctr0, ctr1 = rep0["counters"], rep1["counters"]

    def engine_ms(name):    # the engine's own clock, mean over the window
        n = rep1[name].get("count", 0) - rep0[name].get("count", 0)
        return 1e3 * (rep1[name].get("sum", 0.0)
                      - rep0[name].get("sum", 0.0)) / n if n > 0 else None

    harness.line(
        "serve", tokens=m["tokens"], attempted=m["attempted"],
        failed=m["failed"], in_flight_at_cut=m["in_flight_at_cut"],
        backlog_mid=m["backlog_mid"], backlog_end=m["backlog_end"],
        itl_ms_median=1e3 * float(np.median(m["gaps"])) if m["gaps"] else None,
        itl_samples=len(m["gaps"]),
        ttft_ms_median=1e3 * float(np.median(m["ttft"])) if m["ttft"] else None,
        ttft_samples=len(m["ttft"]),
        late_ms_median=1e3 * float(np.median(m["late"])) if m["late"] else None,
        engine_ttft_ms_mean=engine_ms("ttft"),
        engine_queue_wait_ms_mean=engine_ms("queue_wait"),
        engine_steps=rep1["slot_occupancy"].get("count", 0)
        - rep0["slot_occupancy"].get("count", 0),
        admitted=ctr1["admitted"] - ctr0["admitted"],
        rejected=ctr1["rejected"] - ctr0["rejected"],
        prefill_tokens=ctr1["prefill_tokens"] - ctr0["prefill_tokens"],
        programs_warmed=warm["programs"], driver_errors=len(fe.driver_errors),
        compiles_in_window=compiled_in_window, resident_tokens=resident)
    obs = {"engine_report": (rep0, rep1), "engine": eng,
           "generator_late": m["late"], "work": {}}
    if resident is not None:
        obs["work"]["decode_bytes_per_step"] = flops.decode_bytes_per_step(
            cfg, resident)
    return {
        "correct": bool(ok and compiled_in_window == 0),
        "attempted": m["attempted"], "failed": m["failed"],
        "end_to_end": e2e, "obs": obs, "trace": tw,
    }
