"""The program's own instruments, on the CPU: the serving loop's phase
spans land in a ``jax.profiler`` trace on one host line, named without
what varies (bucket, request and step are stats), beside the calls of
the programs by their own names; the window-wide histograms count what
they say; the compiled programs
carry the scopes ``attn_core``, ``lm_head``, ``loss`` and ``optimizer``
and are still named ``step`` and ``_decode_body``; scopes change no
result.
"""
import contextlib
import glob
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import profiler
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    Histogram,
    PagedServingEngine,
    ServingEngine,
    ServingFrontend,
    stream_generate,
)

DRIVER_SPANS = (
    "frontend::lock_wait", "serving::step", "serving::admit",
    "serving::grow_pages", "serving::decode_inputs",
    "serving::decode_step", "serving::read", "serving::emit",
    "serving::step_tail", "serving::prefill", "serving::adopt",
    "serving::first_token",
)


def _tiny(**kw):
    paddle.seed(5)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, **kw,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


@pytest.fixture(scope="module")
def net():
    return _tiny()


def _paged(net):
    return PagedServingEngine(net, max_batch_size=2, max_seq_len=64,
                              min_bucket=8, page_size=8,
                              demand_paging=True)


@pytest.fixture(scope="module")
def traced(net, tmp_path_factory):
    """Three requests through a front end under a profiler trace: the
    host lines as ``{line index: [(name, start, end, stats)]}`` of the
    ``::`` spans and the programs' calls, and the engine's report."""
    from jax.profiler import ProfileData

    eng = _paged(net)
    fe = ServingFrontend(eng).start()
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    try:
        threads = [
            threading.Thread(target=stream_generate, args=(
                "127.0.0.1", fe.port,
                {"input_ids": list(range(1, n)), "max_new_tokens": 6}))
            for n in (5, 7, 6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        # the driver first: a stream ends inside the last step's emit,
        # and a span still open when the trace stops is lost
        fe.stop(close_engine=True)
        jax.profiler.stop_trace()
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            spans = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                 dict(ev.stats))
                for ev in ln.events
                if ev.name.startswith(("serving::", "frontend::",
                                       "PjitFunction("))
            ]
            if spans:
                lines[i] = spans
    return lines, eng.metrics.report(), eng.step_count


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_driver_spans_share_one_host_line(traced):
    lines, _, _ = traced
    driver = [sp for sp in lines.values()
              if any(n == "serving::step" for n, *_ in sp)]
    assert len(driver) == 1  # one thread steps the engine
    names = {n for n, *_ in driver[0]}
    for span in DRIVER_SPANS:
        assert span in names, span
    # one key a phase: nothing that varies is part of a name
    assert not [n for n in names if "::" in n and re.search(r"\d", n)]
    # a handler thread's wait is a histogram sample, not a span: the
    # front end's spans are all the driver's
    for sp in lines.values():
        if sp is not driver[0]:
            assert not any(n.startswith("frontend::") for n, *_ in sp)


def test_phases_nest_inside_the_step(traced):
    lines, _, steps = traced
    spans, = [sp for sp in lines.values()
              if any(n == "serving::step" for n, *_ in sp)]
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert len(by["serving::step"]) == steps
    for phase in ("serving::admit", "serving::grow_pages",
                  "serving::decode_inputs", "serving::decode_step",
                  "serving::emit", "serving::step_tail"):
        for sp in by[phase]:
            assert _inside(sp, by["serving::step"]), phase
    assert len(by["serving::prefill"]) == len(by["serving::adopt"]) == 3
    for sp in by["serving::prefill"] + by["serving::adopt"]:
        assert _inside(sp, by["serving::admit"])
    # the blocking read is a span of its own, inside the launch's span:
    # an admission waits for none, and nothing but launches lies in it
    assert "serving::settle" not in by
    for sp in by["serving::read"]:
        assert _inside(sp, by["serving::decode_step"])
        assert not _inside(sp, by["serving::admit"])
    # an admitted row's first token is read after the iteration's
    # launch and the read of the step before
    assert len(by["serving::first_token"]) == 3
    for sp in by["serving::first_token"]:
        assert _inside(sp, by["serving::step"])
        assert not _inside(sp, by["serving::admit"]
                           + by["serving::decode_step"])
        assert any(d[2] <= sp[1] and _inside(d, [s for s in
                   by["serving::step"] if _inside(sp, [s])])
                   for d in by["serving::decode_step"])
    for sp in by["frontend::lock_wait"]:
        assert not _inside(sp, by["serving::step"])
    # phases of one step follow one another and do not overlap
    flat = sorted(sp for p in ("serving::decode_inputs",
                               "serving::decode_step", "serving::emit")
                  for sp in by[p])
    ordered = sorted(flat, key=lambda sp: sp[1])
    for a, b in zip(ordered, ordered[1:]):
        assert a[2] <= b[1]


def test_span_keywords_become_event_stats(traced):
    lines, _, steps = traced
    spans, = [sp for sp in lines.values()
              if any(n == "serving::step" for n, *_ in sp)]
    for name in ("serving::step", "serving::decode_step"):
        got = sorted(st["step"] for n, _, _, st in spans if n == name)
        assert got == list(range(steps)), name
    # a read carries the number of the step it reads, which that step's
    # launch carried: every read pairs with one launch before it
    launched = {st["step"]: end for n, _, end, st in spans
                if n == "serving::decode_step"}
    reads = [(st["step"], start) for n, start, _, st in spans
             if n == "serving::read"]
    assert len({step for step, _ in reads}) == len(reads)
    for step, start in reads:
        assert launched[step] <= start
    # what varies from admission to admission: the bucket and the request
    for name in ("serving::prefill", "serving::adopt"):
        stats = [st for n, _, _, st in spans if n == name]
        assert [st["bucket"] for st in stats] == [8, 8, 8], name
        assert len({st["rid"] for st in stats}) == 3, name
    by_rid = {}
    for n, _, _, st in spans:
        if n in ("serving::prefill", "serving::adopt"):
            by_rid.setdefault(st["rid"], set()).add(n)
    assert all(len(v) == 2 for v in by_rid.values())


def test_programs_are_called_by_names_of_their_own(traced):
    lines, _, _ = traced
    spans, = [sp for sp in lines.values()
              if any(n == "serving::step" for n, *_ in sp)]
    called = {n for n, *_ in spans if n.startswith("PjitFunction(")}
    assert {"PjitFunction(prefill_body)", "PjitFunction(adopt_body)",
            "PjitFunction(_decode_body)"} <= called
    assert "PjitFunction(body)" not in called


def test_histogram_counts_follow_from_the_run(traced):
    _, rep, steps = traced
    assert rep["submit_wait"]["count"] == 3       # one a request
    assert rep["prefill"]["count"] == 3           # one an admission
    assert rep["counters"]["admitted"] == 3
    # every launched step is read once (no request ends on EOS, so
    # none is dropped); a launch starts a host_gap sample when a
    # blocking read, a step's or a first token's, returned since the
    # launch before it: not the first of a busy stretch, and a row's
    # last step launches nothing (the exact count is pinned in
    # test_no_host_gap_sample_across_an_idle_engine)
    assert rep["slot_occupancy"]["count"] == steps
    launches = rep["resident_tokens"]["count"]
    assert rep["read_wait"]["count"] == launches < steps
    assert 1 <= rep["host_gap"]["count"] <= launches - 1
    assert 1 <= rep["counters"]["steps_overlapped"] <= launches - 1
    for name in ("host_gap", "read_wait", "prefill", "submit_wait"):
        assert rep[name]["sum"] > 0.0
        assert rep[name]["unit"] == "s"


def test_no_host_gap_sample_across_an_idle_engine(net):
    ticks = iter(range(10_000))
    eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                        min_bucket=8, clock=lambda: float(next(ticks)))
    eng.generate([np.arange(1, 6)[None]], max_new_tokens=4)
    first = eng.step_count
    # four tokens: the prefill's and three decode launches, of which
    # the first alone follows no read (the first token is read behind
    # it, the first step behind the second); the fourth step reads the
    # last and launches nothing
    assert first == 4
    assert eng.metrics.host_gap.count == 2
    assert eng._read_done is None     # the last row left: idle
    assert eng._in_flight is None
    for _ in range(50):               # the clock runs on meanwhile
        eng.clock()
    eng.generate([np.arange(1, 8)[None]], max_new_tokens=4)
    assert eng.step_count - first == 4
    assert eng.metrics.host_gap.count == 4
    # on the engine's clock, and never the idle stretch in between
    assert eng.metrics.host_gap.snapshot()["max"] < 20
    eng.close()


def test_histogram_has_no_profiler_export(monkeypatch):
    calls = []
    monkeypatch.setattr(profiler, "record_span",
                        lambda *a, **k: calls.append(a))
    hist = Histogram("x")
    hist.observe(0.25)
    assert hist.count == 1 and calls == []
    assert not hasattr(hist, "_export")


# ------------------------------------------------------------- scopes
def _locations(lowered):
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


def _has_scope(locs, scope):
    """``scope`` as one component of an operation's path."""
    return any(scope in re.split(r"[/()]", loc) for loc in locs)


def _train_step(net):
    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, 64]),
                               labels.reshape([-1]))

    net.train()
    opt = paddle.optimizer.AdamW(
        1e-3, parameters=net.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    return paddle.jit.CompiledTrainStep(net, loss_fn, opt)


def _batch():
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randint(0, 64, (2, 8)).astype("int32"))
    y = paddle.to_tensor(rng.randint(0, 64, (2, 8)).astype("int32"))
    return x, y


def test_train_step_carries_the_four_scopes():
    net = _tiny()
    step = _train_step(net)
    x, y = _batch()
    step([x], [y])
    params = {k: p.value for k, p in net.named_parameters()}
    buffers = {k: b.value for k, b in net.named_buffers()}
    try:
        locs = _locations(step._step_fn.lower(*step._step_args_sds))
    finally:
        net.load_functional_state(params, buffers)
    for scope in ("optimizer", "loss", "lm_head", "attn_core"):
        assert _has_scope(locs, scope), scope
    # the backward pass keeps the forward's path; the transforms wrap
    # its outermost component: transpose(jvp(model))/0/self_attn/...
    back = {loc for loc in locs if "transpose(jvp(" in loc}
    for scope in ("attn_core", "lm_head", "loss"):
        assert _has_scope(back, scope), scope
    assert not _has_scope(back, "optimizer")
    # module paths come from the names the layers are registered under
    assert any("/self_attn/q_proj/" in loc for loc in locs)
    assert any("/mlp/" in loc for loc in locs)


@pytest.mark.parametrize("engine_cls", [ServingEngine, PagedServingEngine])
def test_decode_program_carries_attn_core_and_lm_head(net, engine_cls):
    eng = engine_cls(net, max_batch_size=2, max_seq_len=64, min_bucket=16)
    try:
        locs = _locations(eng._decode_fn.lower(*eng._decode_example_args()))
    finally:
        eng._restore_net_state()
    assert _has_scope(locs, "attn_core") and _has_scope(locs, "lm_head")
    # the GQA repeat, the cache write and the SDPA are inside attn_core;
    # the projections on either side are not
    assert any("attn_core" in loc and "dot_general" in loc for loc in locs)
    assert not any("attn_core" in loc and "q_proj" in loc for loc in locs)
    eng.close()


def test_tied_head_and_training_path_are_scoped():
    tied = _tiny(tie_word_embeddings=True)
    assert tied.lm_head is None

    def fwd(ids):
        return tied(paddle.Tensor(ids)).value

    locs = _locations(jax.jit(fwd).lower(jnp.zeros((1, 8), jnp.int32)))
    assert _has_scope(locs, "lm_head") and _has_scope(locs, "attn_core")
    assert any("embed_tokens" in loc for loc in locs)


def test_program_names_the_benchmark_matches_are_pinned(net):
    """``train_step_roofline`` matches ``jit_step`` and
    ``decode_step_roofline`` ``decode_body`` in the trace's module
    names, which come from these functions' names."""
    eng = _paged(net)
    try:
        low = eng._decode_fn.lower(*eng._decode_example_args())
    finally:
        eng._restore_net_state()
    assert eng._decode_fn.__name__ == "_decode_body"
    assert "jit__decode_body" in low.as_text()[:400]
    eng.close()
    tnet = _tiny()
    step = _train_step(tnet)
    x, y = _batch()
    step([x], [y])
    assert step._step_fn.__name__ == "step"


def test_scopes_change_no_result(monkeypatch):
    x, y = _batch()

    def run():
        net = _tiny()
        step = _train_step(net)
        losses = [np.asarray(step([x], [y])[0].numpy()) for _ in range(3)]
        net.eval()
        eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                            min_bucket=8)
        toks = eng.generate([np.arange(1, 7)[None]], max_new_tokens=5)
        eng.close()
        return losses, list(toks[0].tokens), {
            k: np.asarray(p.value) for k, p in net.named_parameters()}

    with_scopes = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = run()
    assert with_scopes[1] == without[1]
    for a, b in zip(with_scopes[0], without[0]):
        assert a.tobytes() == b.tobytes()
    for k, v in with_scopes[2].items():
        assert v.tobytes() == without[2][k].tobytes(), k


def test_sublayers_learn_the_name_they_are_registered_under():
    class Block(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = paddle.nn.Linear(4, 4)
            self.add_sublayer("gate", paddle.nn.Linear(4, 4))
            self.stack = paddle.nn.LayerList(
                [paddle.nn.Linear(4, 4) for _ in range(2)])

        def forward(self, x):
            return self.stack[1](self.gate(self.proj(x)))

    blk = Block()
    assert blk.proj._scope_name == "proj"
    assert blk.gate._scope_name == "gate"
    assert [l._scope_name for l in blk.stack] == ["0", "1"]
    assert "_scope_name" not in blk.__dict__    # a root opens no scope
    locs = _locations(jax.jit(
        lambda a: blk(paddle.Tensor(a)).value).lower(jnp.ones((2, 4))))
    for path in ("proj", "gate", "1"):
        assert _has_scope(locs, path), path


def test_submit_wait_covers_the_wait_for_the_drivers_lock(net, monkeypatch):
    """``submit_wait`` runs from the request received to
    ``engine.submit`` returned, so it holds the handler's wait for the
    lock the driver steps under: a lock held for ``held`` seconds after
    the handler took its first stamp gives a sample of at least that."""
    import time

    from paddle_tpu.serving import http_frontend

    eng = ServingEngine(net, max_batch_size=2, max_seq_len=64,
                        min_bucket=8)
    fe = ServingFrontend(eng).start()
    stamped, parse = threading.Event(), http_frontend.parse_traceparent

    def after_the_stamp(header):    # the first call after t_recv
        stamped.set()
        return parse(header)

    monkeypatch.setattr(http_frontend, "parse_traceparent", after_the_stamp)
    held = 0.05
    try:
        with fe._lock:              # the driver's lock, as a step holds it
            t = threading.Thread(target=stream_generate, args=(
                "127.0.0.1", fe.port,
                {"input_ids": [1, 2, 3, 4], "max_new_tokens": 2}))
            t.start()
            assert stamped.wait(60)
            time.sleep(held)
            assert eng.metrics.submit_wait.count == 0   # still waiting
        t.join(120)
        assert not t.is_alive()
    finally:
        fe.stop(close_engine=True)
    waits = eng.metrics.submit_wait.snapshot()
    assert waits["count"] == 1
    assert waits["min"] >= held
