"""paddle_tpu.serving — continuous batching over the bucketed KV pool.

The strong check: a 2-slot engine fed 4 staggered requests must admit
late requests into slots freed by early completions WITHOUT stalling
in-flight sequences, and every request's token stream must be
exact-equal to a standalone ``net.generate`` run — continuous batching
is a scheduling optimization, never an accuracy trade.
"""
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    KVCachePool,
    PagedKVPool,
    PagedServingEngine,
    PagesExhausted,
    REASON_QUEUE_FULL,
    REASON_SHAPE_MISMATCH,
    REASON_TIMEOUT,
    REASON_TOO_LONG,
    Request,
    Scheduler,
    ServingEngine,
    ServingFrontend,
    ServingMetrics,
    bucket_for,
    stream_generate,
)

RNG = np.random.RandomState(7)


@pytest.fixture(scope="module")
def net():
    paddle.seed(5)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
    )
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def gqa_net():
    """Two KV heads under four query heads, as both benchmark models
    group theirs (8 under 32): the cache paths' grouped contraction."""
    paddle.seed(6)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2,
    )
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


# ------------------------------------------------------------ the big one
def test_continuous_batching_exact_vs_generate(net):
    """2 slots, 4 staggered requests: late requests ride slots freed by
    early completions; tokens exact-equal standalone generate; metrics
    nonzero; zero slot leaks."""
    eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                        min_bucket=8)
    prompts = [RNG.randint(0, 64, (1, L)) for L in (6, 5, 7, 9)]
    max_news = [3, 9, 6, 8]  # staggered completion frees slots early
    handles = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
    eng.run_until_idle()

    for h, p, m in zip(handles, prompts, max_news):
        assert h.status == "DONE"
        # same default cache dtype both sides -> bit-identical decode
        want = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=m).numpy())[0]
        np.testing.assert_array_equal(h.output_ids, want)

    # continuous batching actually happened: the first two requests
    # were admitted immediately, the last two only once a slot freed —
    # while another sequence was still mid-decode (overlap, not phases)
    steps = [h.admitted_step for h in handles]
    assert steps[0] == 0 and steps[1] == 0
    assert steps[2] > 0 and steps[3] > steps[2]
    overlap = handles[1].finished_step
    assert steps[2] < overlap  # r2 decoded alongside still-running r1

    # metrics: nonzero TTFT/ITL samples; zero slot leaks
    assert eng.metrics.ttft.count == 4
    assert eng.metrics.itl.count > 0
    assert all(s > 0 for s in eng.metrics.ttft._samples)
    assert eng.metrics.completed.value == 4
    assert eng.metrics.tokens_out.value == sum(max_news)
    assert eng.pool.occupancy == 0
    assert eng.active_slots == 0


def test_engine_eos_early_stop_frees_slot(net):
    """An EOS-terminated sequence retires early; its tokens match the
    generate prefix up to and including the first eos."""
    prompt = RNG.randint(0, 64, (1, 6))
    free = np.asarray(net.generate(
        Tensor(jnp.asarray(prompt)), max_new_tokens=6).numpy())[0]
    eos = int(free[8])  # the 3rd generated token becomes the eos
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                        min_bucket=8)
    h = eng.submit(prompt, 6, eos_token_id=eos)
    eng.run_until_idle()
    assert h.status == "DONE"
    assert h.tokens[-1] == eos
    assert len(h.tokens) <= 6
    np.testing.assert_array_equal(
        np.asarray(h.tokens), free[6:6 + len(h.tokens)]
    )
    assert eng.pool.occupancy == 0


def test_engine_sampling_reproducible(net):
    """Sampled serving is seed-reproducible run-to-run."""
    prompt = RNG.randint(0, 64, (1, 5))

    def run():
        eng = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                            min_bucket=8, do_sample=True,
                            temperature=0.8, top_k=8, seed=11)
        h = eng.submit(prompt, 6)
        eng.run_until_idle()
        return h.tokens

    assert run() == run()


def test_engine_rejects_too_long(net):
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=32,
                        min_bucket=8)
    h = eng.submit(RNG.randint(0, 64, (1, 30)), 8)  # 38 > 32
    assert h.status == "REJECTED" and h.reason == REASON_TOO_LONG
    assert eng.metrics.rejected.by_label() == {REASON_TOO_LONG: 1}
    assert eng.scheduler.depth == 0


def test_engine_deadline_timeout(net):
    """Clock injection: a queued request whose deadline passes before a
    slot frees is failed without running; metrics count it."""
    t = [0.0]
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                        min_bucket=8, clock=lambda: t[0])
    h1 = eng.submit(RNG.randint(0, 64, (1, 6)), 8)
    h2 = eng.submit(RNG.randint(0, 64, (1, 6)), 4, deadline_s=5.0)
    eng.step()  # h1 admitted into the only slot
    t[0] = 10.0  # h2's deadline passes while queued
    eng.run_until_idle()
    assert h1.status == "DONE" and len(h1.tokens) == 8
    assert h2.status == "TIMEOUT" and h2.tokens == []
    assert eng.metrics.timeouts.value == 1
    assert eng.pool.occupancy == 0


# ------------------------------------------------------------- scheduler
def test_scheduler_backpressure_bounded_queue():
    s = Scheduler(max_queue_size=2)
    s.submit(Request(np.arange(4), 4))
    s.submit(Request(np.arange(4), 4))
    from paddle_tpu.serving import RejectedError

    with pytest.raises(RejectedError) as ei:
        s.submit(Request(np.arange(4), 4))
    assert ei.value.reason == REASON_QUEUE_FULL
    assert ei.value.handle.status == "REJECTED"
    assert s.depth == 2


def test_scheduler_priority_then_fifo():
    s = Scheduler(max_queue_size=8)
    a = s.submit(Request(np.arange(4), 4, priority=0))
    b = s.submit(Request(np.arange(4), 4, priority=5))
    c = s.submit(Request(np.arange(4), 4, priority=5))
    d = s.submit(Request(np.arange(4), 4, priority=1))
    order = [s.pop_next() for _ in range(4)]
    assert order == [b, c, d, a]  # priority desc, FIFO within


def test_scheduler_token_budget_no_skip():
    """Strict ordering: a head that exceeds the budget blocks admission
    (delayed, never starved) rather than letting later requests jump."""
    s = Scheduler(max_queue_size=8)
    big = s.submit(Request(np.arange(20), 20))   # 40 tokens
    s.submit(Request(np.arange(2), 2))           # 4 tokens
    assert s.pop_next(token_budget=10) is None
    assert s.pop_next(token_budget=100) is big


# --------------------------------------------------------------- kv pool
def test_bucket_rounding():
    assert bucket_for(1, min_bucket=16) == 16
    assert bucket_for(16, min_bucket=16) == 16
    assert bucket_for(17, min_bucket=16) == 32
    assert bucket_for(100, min_bucket=16) == 128
    assert bucket_for(100, min_bucket=16, max_seq_len=100) == 100
    with pytest.raises(ValueError, match="exceeds"):
        bucket_for(101, min_bucket=16, max_seq_len=100)


def test_kv_pool_alloc_free_reuse_and_occupancy(net):
    pool = KVCachePool(net.config, min_bucket=8, max_seq_len=128)
    assert str(pool.dtype) == "bfloat16"  # serving default
    blk = pool.alloc(10)
    assert blk.bucket == 16
    assert blk.caches[0][0].shape == (1, 16, net.config.kv_heads,
                                      net.config.head_dim)
    assert blk.caches[0][0].dtype == jnp.bfloat16
    assert pool.occupancy == 1
    pool.free(blk)
    assert pool.occupancy == 0
    blk2 = pool.alloc(12)  # same bucket -> recycled, no new alloc
    assert blk2 is blk
    assert pool.reuse_hits == 1 and pool.allocs == 1
    with pytest.raises(ValueError, match="double-free"):
        pool.free(blk2), pool.free(blk2)
    stats = pool.stats()
    assert stats["reserved_bytes"] > 0
    assert stats["occupancy"] == 0


def test_kv_pool_fp32_override(net):
    pool = KVCachePool(net.config, dtype="float32", min_bucket=8,
                       max_seq_len=64)
    assert pool.alloc(8).caches[0][0].dtype == jnp.float32


# --------------------------------------------------------------- metrics
def test_metrics_percentiles_and_profiler_export(net):
    m = ServingMetrics()
    for v in (0.1, 0.2, 0.3, 0.4):
        m.ttft.observe(v)
    assert m.ttft.count == 4
    assert m.ttft.percentile(0) == pytest.approx(0.1)
    assert m.ttft.percentile(100) == pytest.approx(0.4)
    assert m.ttft.snapshot()["p50"] in (0.2, 0.3)
    assert "ttft" in m.render()

    # a histogram sample is no profiler span; what a RECORD window
    # holds of the serving loop is its phases, each a RecordEvent
    from paddle_tpu import profiler

    eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                        min_bucket=8)
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    m.itl.observe(0.005)
    eng.generate([RNG.randint(0, 64, (1, 6))], max_new_tokens=3)
    summary = prof.summary()
    prof.stop()
    eng.close()
    assert "serving::itl" not in summary
    for phase in ("serving::step", "serving::admit", "serving::prefill",
                  "serving::adopt", "serving::decode_inputs",
                  "serving::decode_step", "serving::read",
                  "serving::emit", "serving::step_tail"):
        assert phase in summary, phase


# ------------------------------------------------- saved-artifact serving
def test_predictor_into_engine(net, tmp_path):
    """jit.save decode artifact -> create_predictor -> into_engine():
    the request surface serves the fixed-shape program, token-exact."""
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.generation import GreedyDecoder
    from paddle_tpu.static import InputSpec

    dec = GreedyDecoder(net, max_new_tokens=4)
    prefix = str(tmp_path / "srv")
    dec.save(prefix, input_spec=[InputSpec([2, 5], "int32", "ids")])
    pred = create_predictor(
        Config(prefix + ".stablehlo", prefix + ".pdiparams")
    )
    eng = pred.into_engine()
    assert (eng.batch_size, eng.prompt_len) == (2, 5)

    prompts = [RNG.randint(0, 64, (1, 5)).astype(np.int32)
               for _ in range(3)]
    handles = [eng.submit(p) for p in prompts]
    bad = eng.submit(RNG.randint(0, 64, (1, 9)))  # wrong prompt length
    assert bad.status == "REJECTED"
    assert bad.reason == REASON_SHAPE_MISMATCH
    eng.run_until_idle()
    for h, p in zip(handles, prompts):
        assert h.status == "DONE"
        want = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=4).numpy())[0]
        np.testing.assert_array_equal(h.output_ids, want)
    assert eng.metrics.completed.value == 3
    assert eng.metrics.ttft.count == 3


# ----------------------------------------------------------- serve_bench
def test_serve_bench_offline_trace():
    """The Poisson replay driver runs end to end on CPU and reports a
    coherent summary."""
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.serve_bench import main

    out = main([
        "--requests", "6", "--rate", "200", "--max-batch", "2",
        "--max-seq", "64", "--prompt-min", "4", "--prompt-max", "10",
        "--new-min", "2", "--new-max", "5", "--hidden", "32",
        "--layers", "1", "--heads", "2", "--vocab", "64",
        "--min-bucket", "8", "--no-warmup", "--json",
    ])
    assert out["completed"] == 6
    assert out["tokens_out"] >= 12  # >= new-min per request
    assert out["decode_tok_s"] > 0
    assert out["pool"]["occupancy"] == 0
    assert out["metrics"]["ttft"]["count"] == 6


# ------------------------------------------------------------ CI tooling
def test_vmesh_streams_phase_lines_live():
    """run_in_virtual_cpu_mesh(stream=True) forwards child lines to the
    parent's stdout as they are produced AND still returns the captured
    output (the round-5 dryrun evidence fix)."""
    from tools.vmesh import run_in_virtual_cpu_mesh

    r = run_in_virtual_cpu_mesh(
        1,
        "import sys; print('phase-1 OK'); sys.stdout.flush(); "
        "print('phase-2 OK')",
        cwd="/root/repo", timeout=120, stream=True,
    )
    assert r.returncode == 0
    assert "phase-1 OK" in r.stdout and "phase-2 OK" in r.stdout


def test_vmesh_stream_timeout_preserves_completed_lines():
    """A timeout mid-payload still surfaces the lines already printed —
    the captured tail shows every completed phase."""
    from tools.vmesh import run_in_virtual_cpu_mesh

    with pytest.raises(subprocess.TimeoutExpired) as ei:
        run_in_virtual_cpu_mesh(
            1,
            "import sys, time; print('phase-1 OK'); "
            "sys.stdout.flush(); time.sleep(300)",
            # the payload imports nothing heavy: 4 s is process spawn +
            # one print, and every second here is pure tier-1 wall time
            cwd="/root/repo", timeout=4, stream=True,
        )
    assert "phase-1 OK" in (ei.value.output or "")


# ------------------------------------------------- review regressions
def test_engine_empty_prompt_rejected_without_slot_leak(net):
    """An empty prompt must fail fast at submit — not crash mid-step
    with a claimed slot stranded (which wedges a small engine)."""
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=32,
                        min_bucket=8)
    with pytest.raises(ValueError, match="at least one"):
        eng.submit(np.zeros((1, 0), np.int32), 4)
    h = eng.submit(RNG.randint(0, 64, (1, 5)), 3)  # engine still works
    eng.run_until_idle()
    assert h.status == "DONE"
    assert eng.pool.occupancy == 0


def test_scheduler_lazy_pop_expiry_reaches_drain():
    """A deadline that passes between the sweep and pop_next (e.g.
    while a prefill compiles) is expired lazily by pop_next; the handle
    must still surface through drain_timed_out so engines count it."""
    t = [0.0]
    s = Scheduler(max_queue_size=4, clock=lambda: t[0])
    h = s.submit(Request(np.arange(4), 4, deadline_s=5.0))
    assert s.sweep_expired() == []  # not expired at sweep time
    t[0] = 10.0                     # ...but expires before the pop
    assert s.pop_next() is None
    assert h.status == "TIMEOUT"
    drained = s.drain_timed_out()
    assert drained == [h]
    assert s.drain_timed_out() == []  # drained exactly once


def test_histogram_window_bounded_running_totals():
    from paddle_tpu.serving import Histogram

    hist = Histogram("x", maxlen=8)
    for i in range(20):
        hist.observe(float(i))
    assert hist.count == 20            # running total: every sample
    assert hist.sum == sum(range(20))
    assert len(hist._samples) == 8     # window: bounded memory
    assert hist.percentile(0) == 12.0  # window holds the newest 8


def test_engine_close_cancels_and_releases(net):
    """close(): queued + in-flight requests finish as CANCELLED, every
    slab slot is released (occupancy back to 0), programs dropped."""
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                        min_bucket=8)
    h1 = eng.submit(RNG.randint(0, 64, (1, 5)), 8)
    h2 = eng.submit(RNG.randint(0, 64, (1, 5)), 8)  # queued behind h1
    eng.step()
    assert h1.status == "RUNNING" and len(h1.tokens) >= 1
    eng.close()
    assert h1.status == "CANCELLED" and h1.finished
    assert h2.status == "CANCELLED"
    assert h1.tokens  # partial tokens kept
    assert eng.pool.occupancy == 0
    assert eng.scheduler.depth == 0
    # terminal state is explicit: no silent queueing, no opaque crash
    h3 = eng.submit(RNG.randint(0, 64, (1, 5)), 2)
    assert h3.status == "REJECTED" and h3.reason == "engine_closed"
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()


# ----------------------------------------------------------- paged pool
def test_paged_pool_claim_release_accounting(net):
    pool = PagedKVPool(net.config, page_size=8, num_pages=6,
                       max_seq_len=48)
    assert pool.pages_for(1) == 1
    assert pool.pages_for(8) == 1
    assert pool.pages_for(9) == 2
    assert pool.table_width() == 6
    a = pool.claim(2)
    b = pool.claim(3)
    assert 0 not in a + b  # page 0 is the reserved garbage page
    assert pool.pages_in_use == 5 and pool.free_pages == 1
    with pytest.raises(PagesExhausted):
        pool.claim(2)
    assert pool.exhausted_events == 1
    assert pool.pages_in_use == 5  # failed claim claims nothing
    pool.release(a)
    with pytest.raises(ValueError, match="double release|not claimed"):
        pool.release(a)
    pool.release(b)
    assert pool.pages_in_use == 0
    s = pool.stats()
    assert s["claims"] == 5 and s["releases"] == 5
    assert s["page_bytes"] > 0
    assert s["arena_bytes"] == 7 * s["page_bytes"]  # +1 garbage page
    with pytest.raises(ValueError, match="power of two"):
        PagedKVPool(net.config, page_size=6, num_pages=4)


# ---------------------------------------------------------- paged engine
@pytest.mark.parametrize("which", ["net", "gqa_net"])
def test_paged_engine_exact_vs_slab_and_generate(which, request):
    """The tentpole pin: paged continuous batching (2 rows, 4 staggered
    requests, pages claimed per-length) produces token streams
    exact-equal to BOTH the slab engine and standalone net.generate —
    on the CPU 8-device virtual mesh, like every serving test. Under
    MHA and under GQA, whose three cache branches share one grouped
    contraction."""
    import jax

    net = request.getfixturevalue(which)
    assert jax.device_count() == 8  # the virtual mesh conftest forces
    prompts = [RNG.randint(0, 64, (1, L)) for L in (6, 5, 7, 9)]
    max_news = [3, 9, 6, 8]

    slab = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                         min_bucket=8)
    hs = [slab.submit(p, m) for p, m in zip(prompts, max_news)]
    slab.run_until_idle()

    paged = PagedServingEngine(net, max_batch_size=2, max_seq_len=64,
                               min_bucket=8, page_size=8)
    hp = [paged.submit(p, m) for p, m in zip(prompts, max_news)]
    paged.run_until_idle()

    for h_s, h_p, p, m in zip(hs, hp, prompts, max_news):
        assert h_s.status == "DONE" and h_p.status == "DONE"
        want = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=m).numpy())[0]
        np.testing.assert_array_equal(h_p.output_ids, want)
        np.testing.assert_array_equal(h_p.output_ids, h_s.output_ids)
    # continuous batching happened on the paged engine too
    steps = [h.admitted_step for h in hp]
    assert steps[2] > 0 and steps[3] > steps[2]
    # drained: zero leaked pages, zero leaked blocks
    assert paged.page_pool.pages_in_use == 0
    assert paged.pool.occupancy == 0
    st = paged.page_pool.stats()
    assert st["claims"] == st["releases"] > 0


def test_paged_more_concurrency_than_slab_at_equal_hbm(net):
    """The acceptance pin: at EQUAL resident KV HBM, the paged engine
    admits strictly more concurrent requests for a mixed-length
    workload, because a request claims ceil(total/page) pages instead
    of a full S_max slab row."""
    S_max, ps = 64, 8
    slab = ServingEngine(net, max_batch_size=2, max_seq_len=S_max,
                         min_bucket=8)
    # equal budget: slab = 2 rows x 64 slots = 128 token-slots; paged
    # arena = 16 pages x 8 = 128 token-slots INCLUDING the garbage page
    # (15 usable) — the comparison gives paged no extra bytes
    paged = PagedServingEngine(
        net, max_batch_size=8, max_seq_len=S_max, min_bucket=8,
        page_size=ps, num_pages=15, max_prefills_per_step=None,
    )
    slab_bytes = slab.pool._bytes(S_max, rows=2)
    assert paged.page_pool.arena_bytes() == slab_bytes
    # mixed-length workload: total 24 tokens/request -> 3 pages each
    prompts = [RNG.randint(0, 64, (1, 20)) for _ in range(6)]
    hs = [slab.submit(p, 4) for p in prompts]
    hp = [paged.submit(p, 4) for p in prompts]
    slab.step()
    paged.step()
    slab_conc = slab.active_slots
    paged_conc = paged.active_slots
    assert slab_conc == 2          # a row each, rest queued
    assert paged_conc == 5         # floor(15 pages / 3) concurrent
    assert paged_conc > slab_conc  # the acceptance inequality
    # per-admitted-request resident bytes: paged strictly smaller
    per_req_slab = slab.pool._bytes(S_max)           # full row, always
    per_req_paged = paged.page_pool.request_resident_bytes(24)
    assert per_req_paged < per_req_slab
    assert per_req_paged == 3 * paged.page_pool.page_bytes()
    # and the speedup is not an accuracy trade: drain + exact streams
    slab.run_until_idle()
    paged.run_until_idle()
    for h_s, h_p, p in zip(hs, hp, prompts):
        want = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=4).numpy())[0]
        np.testing.assert_array_equal(h_s.output_ids, want)
        np.testing.assert_array_equal(h_p.output_ids, want)
    assert paged.page_pool.pages_in_use == 0


def test_paged_zero_leak_after_mixed_churn(net):
    """finish + deadline-timeout + close-cancel churn: every page goes
    back (claims == releases, in_use == 0) and the block pool drains."""
    t = [0.0]
    eng = PagedServingEngine(net, max_batch_size=1, max_seq_len=64,
                             min_bucket=8, page_size=8,
                             clock=lambda: t[0])
    h_done = eng.submit(RNG.randint(0, 64, (1, 6)), 2)
    h_run = eng.submit(RNG.randint(0, 64, (1, 5)), 20)
    h_dead = eng.submit(RNG.randint(0, 64, (1, 7)), 4, deadline_s=5.0)
    eng.step()   # h_done admitted + finished (2 tokens in one step)
    eng.step()   # h_run takes the row; h_dead stays queued behind it
    eng.step()
    assert h_done.status == "DONE"
    t[0] = 10.0  # h_dead expires QUEUED (the single row is occupied)
    eng.step()
    assert h_dead.status == "TIMEOUT" and h_dead.tokens == []
    assert h_run.status == "RUNNING"
    eng.close()  # cancels h_run in flight
    assert h_run.status == "CANCELLED" and h_run.tokens
    st = eng.page_pool.stats()
    assert st["pages_in_use"] == 0
    assert st["claims"] == st["releases"] > 0
    assert eng.pool.occupancy == 0


def test_paged_prefill_decode_disaggregation(net):
    """max_prefills_per_step=1 (default): a backlog of prompts admits
    ONE prefill per step, and in-flight sequences keep decoding a token
    every step — long-prompt bursts never stall the decode batch."""
    eng = PagedServingEngine(net, max_batch_size=4, max_seq_len=64,
                             min_bucket=8, page_size=8)
    handles = [eng.submit(RNG.randint(0, 64, (1, 6)), 8)
               for _ in range(3)]
    eng.step()
    assert [h.status for h in handles] == ["RUNNING", "QUEUED", "QUEUED"]
    n0 = len(handles[0].tokens)
    eng.step()  # admits #2; #1 must STILL gain a decode token
    assert handles[1].status == "RUNNING"
    assert len(handles[0].tokens) == n0 + 1
    eng.step()
    assert handles[2].status == "RUNNING"
    assert [h.admitted_step for h in handles] == [0, 1, 2]
    eng.run_until_idle()
    for h in handles:
        assert h.status == "DONE" and len(h.tokens) == 8
    assert eng.page_pool.pages_in_use == 0


def test_paged_geometry_validation(net):
    with pytest.raises(ValueError, match="power of two"):
        PagedServingEngine(net, page_size=6, min_bucket=8,
                           max_seq_len=48)
    with pytest.raises(ValueError, match="min_bucket"):
        PagedServingEngine(net, page_size=16, min_bucket=8,
                           max_seq_len=64)
    with pytest.raises(ValueError, match="multiple"):
        PagedServingEngine(net, page_size=8, min_bucket=8,
                           max_seq_len=60)
    # page_size <= min_bucket is not enough: 8 < 12 but the bucket
    # ladder 12/24/48 is not page-aligned — must fail at construction,
    # not at the first adoption's reshape.
    with pytest.raises(ValueError, match="min_bucket"):
        PagedServingEngine(net, page_size=8, min_bucket=12,
                           max_seq_len=48)


def test_paged_oversized_request_rejected_at_submit(net):
    """A request needing more pages than the whole arena can never be
    admitted — it must be REJECTED too_long at submit, not left at the
    head of the FIFO queue blocking every later request forever."""
    eng = PagedServingEngine(net, max_batch_size=2, max_seq_len=64,
                             min_bucket=8, page_size=8, num_pages=4)
    big = eng.submit(RNG.randint(0, 64, (1, 26)), 10)  # 36 tok > 32
    assert big.status == "REJECTED"
    assert big.reason == REASON_TOO_LONG
    assert eng.scheduler.depth == 0      # never entered the queue
    fits = eng.submit(RNG.randint(0, 64, (1, 20)), 12)  # 32 tok == 32
    assert fits.status == "QUEUED"
    eng.close()


def test_paged_sampling_reproducible(net):
    prompt = RNG.randint(0, 64, (1, 5))

    def run():
        eng = PagedServingEngine(net, max_batch_size=1, max_seq_len=64,
                                 min_bucket=8, page_size=8,
                                 do_sample=True, temperature=0.8,
                                 top_k=8, seed=11)
        h = eng.submit(prompt, 6)
        eng.run_until_idle()
        return h.tokens

    assert run() == run()


# ------------------------------------------- one decode step in flight
def _lag_engine(kind, net, **kw):
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("min_bucket", 8)
    if kind == "slab":
        return ServingEngine(net, **kw)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_prefills_per_step", None)
    return PagedServingEngine(net, **kw)


def _settle(eng):
    """Read and emit the decode step in flight and leave nothing in
    flight (what an admission waited for until it became the new row's
    step in flight itself)."""
    launched, eng._in_flight = eng._in_flight, None
    if launched is not None:
        assert not launched.admitted     # read in their own iteration
        eng._emit(launched, eng._read(launched))


def _drive_serially(eng):
    """The serial order: every launched decode step is read before the
    next launch, so every continuing row's token visits the host
    (``from_host``) and no launch is overlapped. An admission then
    finds nothing in flight, and its first token still reaches its
    first step on the device."""
    while eng.scheduler.depth or eng.active_slots:
        eng.step()
        _settle(eng)


def _ref(net, prompt, max_new):
    return np.asarray(net.generate(
        Tensor(jnp.asarray(prompt)), max_new_tokens=max_new).numpy())[0]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize(
    "kind", ["slab", "paged", "paged-demand", "paged-prefix"])
def test_lagged_streams_equal_serial_order_and_generate(net, kind,
                                                        sampled):
    """Rows of unequal ``max_new_tokens`` (finishes fall on different
    steps, slots turn over mid-run, every later request is admitted
    with a step in flight) through the loop that launches step n+1
    before it reads step n and reads no first token at admission:
    token for token what the serial order gives (each continuing
    row's token and each new row's first fed from the device, not from
    the host), greedy equal to ``generate()``; sampled too, because
    keys are addressed by position. ``paged-prefix``: the prompts share
    a head of two pages, so all but the first are admitted warm, their
    first token the chunked prefill's."""
    kw = dict(max_batch_size=3)
    if sampled:
        kw.update(do_sample=True, temperature=0.8, top_k=8, seed=11)
    if kind == "paged-demand":
        kw.update(demand_paging=True)
    prompts = [RNG.randint(0, 64, (1, L)) for L in (6, 5, 7, 9, 4, 8)]
    max_news = [3, 9, 6, 8, 1, 12]
    if kind == "paged-prefix":
        kw.update(prefix_cache=True)
        head = RNG.randint(0, 64, (1, 16))
        prompts = [np.concatenate([head, p], 1) for p in prompts]

    def run(drive):
        eng = _lag_engine(kind.split("-")[0], net, **kw)
        hs = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        drive(eng)
        assert eng._in_flight is None and eng.active_slots == 0
        assert eng.pool.occupancy == 0
        if kind == "paged-prefix":
            assert eng.chunk_prefills == len(prompts) - 1
        elif kind != "slab":
            assert eng.page_pool.pages_in_use == 0
        rep = eng.metrics.report()
        eng.close()
        return hs, rep

    lagged, rep = run(lambda eng: eng.run_until_idle())
    serial, rep_s = run(_drive_serially)
    # the slots turn over and the engine never drains: every launch but
    # the first was made with a step in flight, admissions or none
    assert rep["counters"]["steps_overlapped"] \
        == rep["resident_tokens"]["count"] - 1
    assert rep_s["counters"]["steps_overlapped"] == 0
    # an admission a request, each timed to its first token's read
    assert rep["prefill"]["count"] == rep_s["prefill"]["count"] \
        == len(prompts)
    # the same work (the positions fed, summed over all launches): no
    # row runs a step more for its last token being known late
    assert rep["resident_tokens"]["sum"] == rep_s["resident_tokens"]["sum"]
    assert rep["counters"]["tokens_out"] == sum(max_news)
    for h, hs, p, m in zip(lagged, serial, prompts, max_news):
        assert h.status == "DONE" and len(h.tokens) == m
        assert h.tokens == hs.tokens
        if not sampled:
            np.testing.assert_array_equal(h.output_ids, _ref(net, p, m))


@pytest.mark.parametrize("nth", [2, 0], ids=["third", "first"])
@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_lagged_eos_extra_step_is_dropped(net, kind, nth):
    """A row that ends on EOS is found one step late: the step launched
    for it meanwhile is dropped (its token never emitted, its page
    given back), whether another row keeps the engine going or the
    engine goes idle under it. ``first``: the EOS is the prefill's own
    token, which the host sees only after the row's first decode step
    was launched; beside another row the admission had a step in
    flight."""
    prompts = [RNG.randint(0, 64, (1, L)) for L in (6, 7)]
    free = [_ref(net, p, 12) for p in prompts]
    eos = int(free[0][6 + nth])          # row 0's generated token nth
    stop = list(free[0][6:]).index(eos) + 1
    kw = dict(max_batch_size=2)
    if kind == "paged":
        kw.update(demand_paging=True, num_pages=8)
    for others in (True, False):
        eng = _lag_engine(kind, net, **kw)
        seen = []
        h1 = eng.submit(prompts[1], 12) if others else None
        if others and not nth:
            for _ in range(3):
                eng.step()
            assert eng._in_flight.nxt is not None
        h0 = eng.submit(prompts[0], 12, eos_token_id=eos,
                        on_token=lambda t, h: seen.append(int(t)))
        eng.run_until_idle()
        assert h0.status == "DONE"
        assert h0.tokens == list(free[0][6:6 + stop]) == seen
        want = stop
        if others:
            assert h1.status == "DONE"
            np.testing.assert_array_equal(h1.output_ids, free[1])
            want += 12
        assert eng.metrics.tokens_out.value == want
        assert eng._in_flight is None
        assert eng.pool.occupancy == 0
        if kind == "paged":
            st = eng.page_pool.stats()
            assert st["pages_in_use"] == 0
            assert eng.page_pool.free_pages == 8
            assert st["claims"] == st["releases"] > 0
        eng.close()


@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_lagged_token_never_reaches_a_readmitted_slot(net, kind):
    """The step in flight remembers the rows it was launched for by
    identity: a row finished while its step ran (a cancel here), whose
    slot is admitted again before that step is read (as every
    admission is: nothing is read for one), hands the new row nothing
    of the old one's. The new row takes the old one's place in the
    record: its prefill is its step in flight."""
    from paddle_tpu.serving.scheduler import CANCELLED

    eng = _lag_engine(kind, net, max_batch_size=2)
    pa, pc, pb = (RNG.randint(0, 64, (1, L)) for L in (6, 7, 5))
    ha, hc = eng.submit(pa, 12), eng.submit(pc, 12)
    for _ in range(3):
        eng.step()
    slot = next(i for i, s in enumerate(eng._seqs)
                if s is not None and s.handle is ha)
    assert eng._in_flight.seqs[slot].handle is ha
    n_a = len(ha.tokens)
    eng._finish(slot, CANCELLED, reason="client_gone")
    hb = eng.submit(pb, 9)
    launched = eng._in_flight
    # the slot is taken again while the old row's token is still on
    # the device, and the new row's first token stays there too
    eng._admit()
    assert eng._seqs[slot].handle is hb and hb.tokens == []
    assert eng._in_flight is launched
    assert launched.seqs[slot] is eng._seqs[slot]
    assert launched.admitted == [slot]
    assert launched.feed is not launched.nxt
    assert eng._launch_pos(slot) == 5 == eng._seqs[slot].pos + 1
    eng.run_until_idle()
    assert ha.status == "CANCELLED" and len(ha.tokens) == n_a
    np.testing.assert_array_equal(hb.output_ids, _ref(net, pb, 9))
    np.testing.assert_array_equal(hc.output_ids, _ref(net, pc, 12))
    assert eng.pool.occupancy == 0
    eng.close()


@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_deadline_timeout_with_a_step_in_flight(net, kind):
    """A running row whose deadline passes while its step is on the
    device: TIMEOUT, its unread token dropped, the other row exact,
    nothing leaked."""
    t = [0.0]
    eng = _lag_engine(kind, net, max_batch_size=2, clock=lambda: t[0])
    pa, pb = RNG.randint(0, 64, (1, 6)), RNG.randint(0, 64, (1, 7))
    ha = eng.submit(pa, 12, deadline_s=5.0)
    hb = eng.submit(pb, 12)
    for _ in range(4):
        eng.step()
    assert eng._in_flight is not None
    assert any(s is not None and s.handle is ha
               for s in eng._in_flight.seqs)
    n_a = len(ha.tokens)
    t[0] = 10.0
    eng.run_until_idle()
    assert ha.status == "TIMEOUT" and ha.reason == REASON_TIMEOUT
    assert ha.tokens == list(_ref(net, pa, 12)[6:6 + n_a])
    np.testing.assert_array_equal(hb.output_ids, _ref(net, pb, 12))
    assert eng.metrics.timeouts.value == 1
    assert eng.metrics.tokens_out.value == n_a + 12
    assert eng._in_flight is None and eng.pool.occupancy == 0
    if kind == "paged":
        assert eng.page_pool.pages_in_use == 0
    eng.close()


@pytest.mark.parametrize("kind", ["slab", "paged", "paged-demand"])
def test_admission_beside_rows_is_an_overlapped_iteration(net, kind):
    """An iteration that admits beside a resident row reads nothing
    before its launches: the prefill and the adoption go behind the
    step in flight, the next decode step behind them (counted as
    overlapped), that step feeds the new row at ``prompt_len`` from the
    device, and only then the step before and the first token are
    read. A request of one token admitted so is fed nothing."""
    kw = {"demand_paging": True} if kind == "paged-demand" else {}
    eng = _lag_engine(kind.split("-")[0], net, max_batch_size=3, **kw)
    m = eng.metrics
    pa, pb, pc = (RNG.randint(0, 64, (1, L)) for L in (6, 8, 5))
    ha = eng.submit(pa, 12)
    for _ in range(3):
        eng.step()
    hb, hc = eng.submit(pb, 6), eng.submit(pc, 1)
    step_n, n_a = eng._in_flight, len(ha.tokens)
    reads, launches = m.read_wait.count, m.resident_tokens.count
    overlapped = m.steps_overlapped.value
    eng._admit()
    # both admitted (a paged engine built by _lag_engine has no cap),
    # nothing read: no token of the step in flight, no first token
    assert hb.status == hc.status == "RUNNING"
    assert m.read_wait.count == reads and len(ha.tokens) == n_a
    assert hb.tokens == hc.tokens == [] and hb.first_token_time is None
    assert m.prefill.count == 1 and m.admitted.value == 3
    assert eng._in_flight is step_n and step_n.nxt is not None
    sb, sc = (next(i for i, s in enumerate(eng._seqs)
                   if s is not None and s.handle is h) for h in (hb, hc))
    assert sorted(step_n.admitted) == sorted([sb, sc])
    assert eng._launch_pos(sb) == 8 and eng._launch_pos(sc) is None
    fed = []
    decode = eng._decode_fn
    eng._decode_fn = lambda *a: (fed.append(
        (np.asarray(a[-1]), np.asarray(a[-2]))), decode(*a))[1]
    eng._decode_once()
    eng._decode_fn = decode
    from_host, prev = fed[0]
    assert not from_host[sb] and prev[sb] == hb.tokens[0]
    assert m.resident_tokens.count == launches + 1
    assert m.steps_overlapped.value == overlapped + 1
    assert eng._in_flight.seqs[sb].handle is hb
    assert eng._in_flight.seqs[sc] is None
    assert len(ha.tokens) == n_a + 1 and len(hb.tokens) == 1
    assert hc.status == "DONE" and len(hc.tokens) == 1
    assert m.prefill.count == 3 and hb.first_token_time is not None
    eng.run_until_idle()
    for h, p, n in ((ha, pa, 12), (hb, pb, 6), (hc, pc, 1)):
        np.testing.assert_array_equal(h.output_ids, _ref(net, p, n))
    assert m.steps_overlapped.value == m.resident_tokens.count - 1
    assert eng.pool.occupancy == 0
    eng.close()


@pytest.mark.parametrize("how", ["deadline", "shed", "device_error"])
def test_a_row_ended_before_its_first_token_is_read(net, how):
    """A row whose first token is still on the device and that a
    deadline, a page shed or an error of its prefill ends: it ends
    with no token, its row and pages go back, the step launched
    meanwhile is dropped for it, and the other row's stream is exact."""
    from paddle_tpu.serving.scheduler import REASON_PAGES_EXHAUSTED

    t = [0.0]
    # 2 pages hold the resident row to its end, 2 the new prompt
    eng = _lag_engine("paged", net, max_batch_size=2, num_pages=4,
                      demand_paging=True, clock=lambda: t[0])
    pa = RNG.randint(0, 64, (1, 6))
    # a prompt of two full pages: its first step writes position 16,
    # into a third page that the arena does not have
    pb = RNG.randint(0, 64, (1, 16 if how == "shed" else 12))
    ha = eng.submit(pa, 9)
    for _ in range(3):
        eng.step()
    hb = eng.submit(pb, 4, deadline_s=5.0)
    if how == "shed":
        eng.step()
        assert hb.status == "CANCELLED"
        assert hb.reason == REASON_PAGES_EXHAUSTED
    else:
        eng._admit()
        slot = eng._in_flight.admitted[0]
        seq = eng._seqs[slot]
        assert seq.handle is hb and seq.first is not None
        if how == "deadline":
            t[0] = 10.0
            eng.step()
            assert hb.status == "TIMEOUT" and hb.reason == REASON_TIMEOUT
        else:
            class Lost:
                def __array__(self, *a, **k):
                    raise RuntimeError("device lost")

            seq.first = (Lost(),) + seq.first[1:]
            eng.step()
            assert hb.status == "REJECTED"
            assert hb.reason == "admission_error:RuntimeError"
            assert eng.metrics.rejected.by_label() == {"admission_error": 1}
    assert hb.tokens == [] and hb.first_token_time is None
    assert eng.metrics.prefill.count == 1
    eng.run_until_idle()
    np.testing.assert_array_equal(ha.output_ids, _ref(net, pa, 9))
    assert eng.metrics.tokens_out.value == 9
    assert eng._in_flight is None and eng.pool.occupancy == 0
    st = eng.page_pool.stats()
    assert st["pages_in_use"] == 0 and st["claims"] == st["releases"]
    eng.close()


def test_pages_exhausted_shed_with_a_step_in_flight(net):
    """Demand growth looks one step ahead of the host (the launch
    writes one position past the unread token): an arena too small for
    both rows sheds the one that cannot grow with ``pages_exhausted``
    while its step is in flight; the survivor's stream is exact and
    every page comes back."""
    from paddle_tpu.serving.scheduler import REASON_PAGES_EXHAUSTED

    eng = _lag_engine("paged", net, max_batch_size=2, num_pages=5,
                      demand_paging=True)
    pa, pb = RNG.randint(0, 64, (1, 10)), RNG.randint(0, 64, (1, 10))
    ha, hb = eng.submit(pa, 30), eng.submit(pb, 30)
    shed_with_lag = []
    finish = eng._finish

    def spy(slot, status, reason=None):
        if reason == REASON_PAGES_EXHAUSTED:
            fl = eng._in_flight
            shed_with_lag.append(
                fl is not None and fl.seqs[slot] is eng._seqs[slot])
        finish(slot, status, reason=reason)

    eng._finish = spy
    eng.run_until_idle()
    assert shed_with_lag == [True]
    shed, winner, pw = (ha, hb, pb) if ha.status == "CANCELLED" \
        else (hb, ha, pa)
    assert shed.status == "CANCELLED"
    assert shed.reason == REASON_PAGES_EXHAUSTED and shed.tokens
    assert winner.status == "DONE"
    np.testing.assert_array_equal(winner.output_ids, _ref(net, pw, 30))
    assert eng.metrics.sheds.by_label() == {REASON_PAGES_EXHAUSTED: 1}
    assert eng.metrics.tokens_out.value == len(shed.tokens) + 30
    assert eng._in_flight is None
    st = eng.page_pool.stats()
    assert st["pages_in_use"] == 0 and st["claims"] == st["releases"]
    eng.close()


def test_unfed_row_leaves_its_pages_alone(net):
    """A row whose last token is in flight rides the next launch as a
    free row does: fed nothing, writing into the garbage page. Its own
    pages stay what its prefill and decode made them: a second request
    with the same prompt adopts them from the prefix cache and is
    exact."""
    eng = _lag_engine("paged", net, max_batch_size=2, prefix_cache=True)
    pa = RNG.randint(0, 64, (1, 16))
    pb = RNG.randint(0, 64, (1, 7))
    ha, hb = eng.submit(pa, 4), eng.submit(pb, 12)
    eng.step()
    slot = next(i for i, s in enumerate(eng._seqs) if s.handle is ha)
    first_page = eng._row_pages[slot][0]
    before = eng._tier_read_page(first_page)
    eng.run_until_idle()   # ha's last step: hb launched, ha unfed
    # a full prompt page: published, so still ha's bytes and no one's
    for was, now in zip(before, eng._tier_read_page(first_page)):
        np.testing.assert_array_equal(was, now)
    hits0 = eng.prefix_cache.hits.value
    hc = eng.submit(pa, 6)
    eng.run_until_idle()
    assert eng.prefix_cache.hits.value == hits0 + 1
    np.testing.assert_array_equal(ha.output_ids, _ref(net, pa, 4))
    np.testing.assert_array_equal(hb.output_ids, _ref(net, pb, 12))
    np.testing.assert_array_equal(hc.output_ids, _ref(net, pa, 6))
    eng.close()


def test_span_ladder_crosses_rungs_without_a_compile(gqa_net):
    """The paged decode read is bounded on the device, inside the ONE
    decode program: a run whose longest row climbs over three rung
    boundaries compiles nothing after ``warmup()``, its program
    inventory is what it was before the ladder, the streams are
    ``generate()``'s, and ``span_tokens`` has a sample a launch, each
    the rung that holds the launch's longest row."""
    from paddle_tpu.quantization import kv as qkv

    eng = _lag_engine("paged", gqa_net, max_batch_size=3, page_size=4)
    rungs = [4 * pages for pages in qkv.span_ladder(eng.table_width)]
    assert rungs == [8, 16, 24, 32, 40, 48, 56, 64]  # eighths of 64
    stats = eng.warmup()
    # decode + (prefill + adopt) per bucket 8..64: one decode program
    assert stats["programs"] == 1 + 2 * 4
    before = dict(eng.trace_guard.compile_counts())
    seen = []
    observe = eng.metrics.span_tokens.observe
    eng.metrics.span_tokens.observe = lambda v: (seen.append(v),
                                                 observe(v))[1]
    prompts = [RNG.randint(0, 64, (1, n)) for n in (6, 3, 5)]
    news = [20, 4, 9]
    hs = [eng.submit(p, m) for p, m in zip(prompts, news)]
    eng.run_until_idle()
    assert dict(eng.trace_guard.compile_counts()) == before
    assert eng.trace_guard.findings == []
    for h, p, m in zip(hs, prompts, news):
        np.testing.assert_array_equal(h.output_ids, _ref(gqa_net, p, m))
    rep = eng.metrics.report()
    assert rep["span_tokens"]["count"] == rep["resident_tokens"]["count"] \
        == len(seen) == max(news) - 1
    # the longest row is launched at positions 6..24: four rungs
    assert seen == [rungs[(6 + i) // 8] for i in range(len(seen))]
    assert sorted(set(seen)) == rungs[:4]
    assert rep["span_tokens"]["sum"] == sum(seen)
    eng.close()


def test_slab_span_is_the_whole_row(net):
    eng = _lag_engine("slab", net, max_batch_size=2)
    eng.submit(RNG.randint(0, 64, (1, 5)), 4)
    eng.run_until_idle()
    rep = eng.metrics.report()
    assert rep["span_tokens"]["count"] == rep["resident_tokens"]["count"] == 3
    assert rep["span_tokens"]["sum"] == 3 * eng.max_seq_len
    eng.close()


@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_full_batch_overlaps_every_step_but_the_first(net, kind):
    """A full batch and no admission after the first iteration: every
    decode launch but the first is made with a step in flight, and the
    clock readings get a sample a step."""
    rows, new = 4, 12
    eng = _lag_engine(kind, net, max_batch_size=rows)
    hs = [eng.submit(RNG.randint(0, 64, (1, 5 + i)), new)
          for i in range(rows)]
    steps = eng.run_until_idle()
    assert all(len(h.tokens) == new for h in hs)
    rep = eng.metrics.report()
    launches = rep["resident_tokens"]["count"]
    # the prefill gives the first token, the last step launches nothing
    assert launches == new - 1 == steps - 1
    assert rep["counters"]["steps_overlapped"] == launches - 1
    assert rep["counters"]["steps_overlapped"] / launches > 0.8
    assert rep["read_wait"]["count"] == launches
    assert rep["itl"]["count"] == rows * launches
    # a sample starts at a blocking read's return, the first tokens'
    # too: at every launch but the first
    assert rep["host_gap"]["count"] == launches - 1
    assert rep["read_wait"]["sum"] >= 0.0
    eng.close()


@pytest.mark.parametrize("kind", ["slab", "paged"])
def test_second_decode_trace_leaves_the_net_concrete(kind):
    """Weights placed over a mesh: the first launch's ``prev`` is an
    upload, the second's comes back from the program, placed as the
    weights are, and jit traces the decode body again. The net holds
    concrete weights after that trace too, and the stream is exact."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    paddle.seed(5)
    mine = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4))
    mine.eval()
    over = NamedSharding(Mesh(np.array(jax.devices()), ("mp",)), P())
    for p in mine.parameters():
        p.value = jax.device_put(p.value, over)
    prompt = RNG.randint(0, 64, (1, 6))
    eng = _lag_engine(kind, mine, max_batch_size=2)
    h = eng.generate([prompt], 6)[0]
    eng.close()
    for _, p in mine.named_parameters():
        assert isinstance(p.value, jax.Array)
        np.asarray(p.value)          # a tracer would raise here
    np.testing.assert_array_equal(h.output_ids, _ref(mine, prompt, 6))


def test_speculation_never_has_a_step_in_flight(net):
    from paddle_tpu.serving import SpeculativeDecoder

    eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                        min_bucket=8,
                        speculative=SpeculativeDecoder(exit_layer=1, k=2))
    hs = [eng.submit(RNG.randint(0, 64, (1, 6)), 8) for _ in range(3)]
    while eng.scheduler.depth or eng.active_slots:
        eng.step()
        assert eng._in_flight is None
        # a round proposes from the row's last token: an admission
        # (the third beside a resident row) takes its first one at once
        assert all(s is None or (s.first is None and s.handle.tokens)
                   for s in eng._seqs)
    assert all(h.status == "DONE" for h in hs)
    rep = eng.metrics.report()
    assert rep["counters"]["steps_overlapped"] == 0
    assert rep["read_wait"]["count"] == 0
    assert rep["counters"]["speculative_rounds"] > 0
    eng.close()


def test_idle_engine_has_nothing_in_flight_and_reloads(net, tmp_path):
    """After ``run_until_idle`` no step is in flight, so a staged
    reload applies at once; one committed mid-run waits for the rows
    AND their last lagged tokens."""
    from paddle_tpu.checkpoint import CheckpointManager

    prompt = RNG.randint(0, 64, (1, 6))
    nets, refs = [], []
    for seed in (5, 9):     # a reload rewrites its net: none shared
        paddle.seed(seed)
        nets.append(LlamaForCausalLM(net.config))
        nets[-1].eval()
        refs.append(_ref(nets[-1], prompt, 8))
    mgr = CheckpointManager(str(tmp_path), network=nets[1],
                            async_saves=False)
    mgr.save(1, blocking=True)
    mgr.close()
    eng = _lag_engine("paged", nets[0], max_batch_size=2)
    h_old = eng.submit(prompt, 8)
    for _ in range(3):
        eng.step()
    assert eng._in_flight is not None
    staged = eng.commit_reload(eng.prepare_reload(str(tmp_path)))
    assert eng.reload_in_progress and eng.weights_version == "v0"
    eng.run_until_idle()
    assert eng._in_flight is None and eng._read_done is None
    assert staged.outcome == "applied" and not eng.reload_in_progress
    np.testing.assert_array_equal(h_old.output_ids, refs[0])
    h_new = eng.generate([prompt], 8)[0]
    assert eng._in_flight is None
    np.testing.assert_array_equal(h_new.output_ids, refs[1])
    assert not np.array_equal(refs[0], refs[1])
    # idle again: the next one applies inside commit_reload itself
    again = eng.reload_weights(str(tmp_path))
    assert again.outcome == "applied" and eng.generation == 2
    eng.close()


# ------------------------------------------------------------- int8 KV
def test_cache_dtype_validated_at_api_seam(net):
    """An unknown cache_dtype must fail AT THE SEAM with the allowed
    set — not deep inside jnp after the cache allocates (satellite)."""
    from paddle_tpu.models.generation import alloc_kv_caches

    p = RNG.randint(0, 64, (1, 5))
    for bad in ("floatnope", "int4", object()):
        with pytest.raises(ValueError, match="cache_dtype"):
            net.generate(Tensor(jnp.asarray(p)), 2, cache_dtype=bad)
    # float16 is a real jnp dtype but NOT an implemented cache dtype
    with pytest.raises(ValueError, match="allowed"):
        alloc_kv_caches(net.config, 1, 8, "float16")
    with pytest.raises(ValueError, match="allowed"):
        ServingEngine(net, max_batch_size=1, max_seq_len=32,
                      min_bucket=8, cache_dtype="float16")
    with pytest.raises(ValueError, match="allowed"):
        PagedKVPool(net.config, page_size=8, num_pages=4,
                    dtype="complex64")


@pytest.mark.slow  # gated every merge by `make quant-smoke` (the
# int8-vs-fp32 agreement budget over HTTP + int8 KV pages)
def test_int8_kv_greedy_agreement_budget_pinned(net):
    """The quantized-KV exactness RATCHET: greedy decode with int8 KV
    must agree with the bf16 stream for at least the pinned prefix, and
    the int8-cache prefill logits must stay within the pinned max-abs
    error of the fp32-cache logits. Measured on this net/prompts:
    agreement 16,16,10 of 16; logit err <= 0.0072. Loosen only with a
    measured reason in the diff."""
    from paddle_tpu.models.generation import alloc_kv_caches, prefill

    PINNED_AGREEMENT = 10   # of 16 greedy tokens, worst prompt
    PINNED_LOGIT_ERR = 0.02
    rng = np.random.RandomState(7)
    for L in (6, 9, 12):
        p = rng.randint(0, 64, (1, L))
        bf = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=16).numpy())[0][L:]
        q8 = np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=16,
            cache_dtype="int8").numpy())[0][L:]
        agree = 0
        for a, b in zip(q8, bf):
            if a != b:
                break
            agree += 1
        assert agree >= PINNED_AGREEMENT, (L, agree, q8, bf)
        lq, _ = prefill(net, jnp.asarray(p),
                        alloc_kv_caches(net.config, 1, L + 4, "int8"))
        lf, _ = prefill(net, jnp.asarray(p),
                        alloc_kv_caches(net.config, 1, L + 4,
                                        "float32"))
        err = float(np.abs(
            np.asarray(lq, np.float32) - np.asarray(lf, np.float32)
        ).max())
        assert err <= PINNED_LOGIT_ERR, (L, err)


@pytest.mark.slow  # gated every merge by `make quant-smoke` (live
# int8 decode == saved artifact == paged int8 HTTP stream, exact)
def test_int8_kv_engines_exact_vs_generate(net):
    """Quantization must not open a gap between the serving paths: the
    slab AND paged engines with ``cache_dtype="int8"`` produce token
    streams EXACT-EQUAL to ``net.generate(cache_dtype="int8")`` — the
    same token quantizes identically everywhere, so serving stays a
    scheduling optimization. Zero page/block leaks after drain."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 64, (1, L)) for L in (6, 5, 9)]
    max_news = [3, 8, 6]
    wants = [
        np.asarray(net.generate(
            Tensor(jnp.asarray(p)), max_new_tokens=m,
            cache_dtype="int8").numpy())[0]
        for p, m in zip(prompts, max_news)
    ]
    slab = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                         min_bucket=8, cache_dtype="int8")
    paged = PagedServingEngine(net, max_batch_size=2, max_seq_len=64,
                               min_bucket=8, page_size=8,
                               cache_dtype="int8")
    for eng in (slab, paged):
        hs = [eng.submit(p, m) for p, m in zip(prompts, max_news)]
        eng.run_until_idle()
        for h, want in zip(hs, wants):
            assert h.status == "DONE"
            np.testing.assert_array_equal(h.output_ids, want)
        assert eng.pool.occupancy == 0
    assert paged.page_pool.pages_in_use == 0
    st = paged.page_pool.stats()
    assert st["claims"] == st["releases"] > 0


def test_int8_kv_equal_hbm_concurrency_at_least_1_8x():
    """The acceptance pin: at the SAME page-arena byte budget (scale
    overhead counted against int8 — no flattery), int8 KV admits
    >= 1.8x the bf16-paged concurrent requests. Head dim 64 here:
    bf16 costs 2 bytes/elem, int8 costs 1 + 4/64 for its per-(token,
    kv-head) fp32 scale -> 1.88x the token-slots, which quantizes to
    9 vs 5 concurrent 3-page requests."""
    import paddle_tpu as paddle

    paddle.seed(9)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=128, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=2,
    )
    m = LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(3)
    bf16 = PagedServingEngine(
        m, max_batch_size=12, max_seq_len=64, min_bucket=8,
        page_size=8, num_pages=15, cache_dtype="bfloat16",
        max_prefills_per_step=None,
    )
    budget = bf16.page_pool.arena_bytes()
    probe = PagedKVPool(cfg, page_size=8, num_pages=1, dtype="int8",
                        max_seq_len=64)
    n_int8 = budget // probe.page_bytes() - 1  # same bytes, more pages
    int8 = PagedServingEngine(
        m, max_batch_size=12, max_seq_len=64, min_bucket=8,
        page_size=8, num_pages=int(n_int8), cache_dtype="int8",
        max_prefills_per_step=None,
    )
    assert int8.page_pool.arena_bytes() <= budget  # never MORE HBM
    # mixed workload: 24 total tokens/request -> 3 pages each
    prompts = [rng.randint(0, 64, (1, 20)) for _ in range(10)]
    hb = [bf16.submit(p, 4) for p in prompts]
    hq = [int8.submit(p, 4) for p in prompts]
    bf16.step()
    int8.step()
    assert bf16.active_slots == 5       # floor(15 usable pages / 3)
    assert int8.active_slots == 9       # floor(29 usable pages / 3)
    assert int8.active_slots >= 1.8 * bf16.active_slots
    # and the capacity win is not an accuracy trade: drain + compare
    bf16.run_until_idle()
    int8.run_until_idle()
    for b, q in zip(hb, hq):
        assert b.status == "DONE" and q.status == "DONE"
    assert bf16.page_pool.pages_in_use == 0
    assert int8.page_pool.pages_in_use == 0


# ----------------------------------------------------- streaming callbacks
def test_streaming_callbacks_token_order_and_single_terminal(net):
    eng = PagedServingEngine(net, max_batch_size=1, max_seq_len=64,
                             min_bucket=8, page_size=8)
    seen, ends = [], []
    h = eng.submit(RNG.randint(0, 64, (1, 6)), 5,
                   on_token=lambda t, hd: seen.append(t),
                   on_event=lambda hd: ends.append(hd.status))
    eng.run_until_idle()
    assert h.status == "DONE"
    assert seen == h.tokens          # every token, in order
    assert ends == ["DONE"]          # terminal fires exactly once


def test_terminal_event_fires_on_every_shed_path(net):
    """The satellite contract: rejects and queue-expiry NEVER leave a
    stream consumer hanging — on_event fires at submit-reject,
    deadline-expiry and close-cancel."""
    t = [0.0]
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=32,
                        min_bucket=8, max_queue_size=1,
                        clock=lambda: t[0])
    ends = {}

    def ender(key):
        return lambda hd: ends.setdefault(key, []).append(
            (hd.status, hd.reason)
        )

    # submit-time reject (too long)
    h1 = eng.submit(RNG.randint(0, 64, (1, 30)), 8,
                    on_event=ender("too_long"))
    assert h1.status == "REJECTED"
    assert ends["too_long"] == [("REJECTED", REASON_TOO_LONG)]
    # queue-full reject
    eng.submit(RNG.randint(0, 64, (1, 5)), 4)  # fills the queue
    h2 = eng.submit(RNG.randint(0, 64, (1, 5)), 4,
                    on_event=ender("full"))
    assert ends["full"] == [("REJECTED", REASON_QUEUE_FULL)]
    # deadline expiry while queued
    eng2 = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                         min_bucket=8, clock=lambda: t[0])
    eng2.submit(RNG.randint(0, 64, (1, 6)), 8)
    h3 = eng2.submit(RNG.randint(0, 64, (1, 6)), 4, deadline_s=5.0,
                     on_event=ender("dead"))
    eng2.step()
    t[0] = 10.0
    eng2.step()
    assert h3.status == "TIMEOUT"
    assert ends["dead"] == [("TIMEOUT", REASON_TIMEOUT)]
    # close-cancel of an in-flight request
    h4 = eng2.scheduler.pop_next()  # none queued; submit + run one
    eng3 = ServingEngine(net, max_batch_size=1, max_seq_len=64,
                         min_bucket=8)
    h5 = eng3.submit(RNG.randint(0, 64, (1, 5)), 8,
                     on_event=ender("closed"))
    eng3.step()
    eng3.close()
    assert h5.status == "CANCELLED"
    assert ends["closed"] == [("CANCELLED", "engine_closed")]
    assert h4 is None


# ------------------------------------------------------- HTTP/SSE frontend
@pytest.fixture(scope="module")
def frontend(net):
    eng = PagedServingEngine(net, max_batch_size=2, max_seq_len=64,
                             min_bucket=8, page_size=8)
    fe = ServingFrontend(eng).start()
    yield fe
    fe.stop(close_engine=True)


@pytest.mark.slow  # gated every merge by `make serve-smoke` (N
# concurrent SSE streams exact-equal net.generate over real sockets)
def test_http_sse_stream_exact(net, frontend):
    """POST -> SSE stream: token events in order, terminal done event,
    tokens exact-equal net.generate, wire metrics recorded."""
    p = RNG.randint(0, 64, (1, 6))
    events, tm = stream_generate(
        "127.0.0.1", frontend.port,
        {"input_ids": [int(t) for t in p[0]], "max_new_tokens": 5},
    )
    toks = [d["token"] for e, d in events if e == "token"]
    want = np.asarray(net.generate(
        Tensor(jnp.asarray(p)), max_new_tokens=5).numpy())[0][6:]
    assert toks == [int(t) for t in want]
    kind, data = events[-1]
    assert kind == "done" and data["status"] == "DONE"
    assert data["tokens"] == toks
    assert [d["index"] for e, d in events if e == "token"] == list(
        range(5)
    )
    assert tm["ttft_s"] > 0
    assert frontend.metrics.wire_ttft.count >= 1


def test_http_reject_statuses_and_health(net, frontend):
    from paddle_tpu.serving import HTTPRejected

    # too-long -> 413 with machine-readable reason, no stream opened
    with pytest.raises(HTTPRejected) as ei:
        stream_generate("127.0.0.1", frontend.port,
                        {"input_ids": [1] * 60, "max_new_tokens": 30})
    assert ei.value.code == 413
    assert ei.value.body["reason"] == REASON_TOO_LONG
    # malformed body -> 400
    with pytest.raises(HTTPRejected) as ei:
        stream_generate("127.0.0.1", frontend.port,
                        {"input_ids": "nope"})
    assert ei.value.code == 400
    # malformed OPTIONAL fields are 400s too — a raw string deadline_s
    # reaching the scheduler heap would poison sweep_expired for every
    # later request (the engine would never decode again).
    for bad in ({"deadline_s": "soon"}, {"deadline_s": -1},
                {"max_new_tokens": 0}, {"priority": [1]}):
        with pytest.raises(HTTPRejected) as ei:
            stream_generate(
                "127.0.0.1", frontend.port,
                {"input_ids": [1, 2, 3], "max_new_tokens": 2, **bad},
            )
        assert ei.value.code == 400, bad
    # and the engine still serves a well-formed request afterwards
    p = RNG.randint(0, 64, (1, 4))
    events, _ = stream_generate(
        "127.0.0.1", frontend.port,
        {"input_ids": [int(t) for t in p[0]], "max_new_tokens": 3},
    )
    assert events[-1][0] == "done" and events[-1][1]["status"] == "DONE"
    # healthz reports pool state
    import http.client
    import json as _json

    conn = http.client.HTTPConnection("127.0.0.1", frontend.port,
                                      timeout=60)
    conn.request("GET", "/healthz")
    hz = _json.loads(conn.getresponse().read())
    conn.close()
    assert hz["engine"] == "PagedServingEngine"
    assert hz["page_pool"]["pages_in_use"] == 0


def test_http_expired_stream_gets_terminal_error_event(net, frontend):
    """A queued request whose deadline passes while its SSE stream is
    open ends with `event: error` carrying the reject reason — and the
    abort counter gains a {reason=timeout} sample."""
    before = frontend.metrics.stream_aborts.by_label().get("timeout", 0)
    p = RNG.randint(0, 64, (1, 6))
    events, _ = stream_generate(
        "127.0.0.1", frontend.port,
        {"input_ids": [int(t) for t in p[0]], "max_new_tokens": 4,
         "deadline_s": 0.0},
    )
    kind, data = events[-1]
    assert kind == "error"
    assert data["reason"] == REASON_TIMEOUT
    assert data["status"] == "TIMEOUT"
    after = frontend.metrics.stream_aborts.by_label().get("timeout", 0)
    assert after == before + 1
