"""What PR 36 added to the serving loop's instruments, on the CPU: an
admission beside resident rows leaves one ``admit_hold`` sample, that
iteration's ``host_gap`` (since PR 37 the host's work of an iteration
that admits, under the step in flight: nothing is read inside it); an
idle engine leaves none; the blocking read is a span of its own, one a
``read_wait`` sample, and so is an admitted row's first-token read; a
phase that serves a request names it (``rid``) and keeps what varies
out of its name, and the request's own spans come from the same calls.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.tracing import Tracer, set_tracer
from paddle_tpu.serving import (
    PagedServingEngine,
    ServingEngine,
    ServingMetrics,
)
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving import paged_engine as paged_mod

ENGINES = [
    (ServingEngine, {}),
    (PagedServingEngine, {"page_size": 8, "demand_paging": True}),
]


class Ticks:
    """A clock that moves ``tick`` a read: whole numbers, so every sum
    is exact."""

    def __init__(self, tick=1.0):
        self.now, self.tick = 0.0, tick

    def __call__(self):
        self.now += self.tick
        return self.now


@pytest.fixture(scope="module")
def net():
    paddle.seed(5)
    net = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2))
    net.eval()
    return net


def _engine(net, cls=ServingEngine, **kw):
    kw = {"max_batch_size": 2, "max_seq_len": 64, "min_bucket": 8, **kw}
    return cls(net, clock=Ticks(), **kw)


def _busy_run(eng):
    """Two requests, the second admitted while the first decodes."""
    a = eng.submit(np.arange(1, 6)[None], 8)
    for _ in range(3):
        eng.step()
    b = eng.submit(np.arange(1, 8)[None], 4)
    eng.run_until_idle()
    assert a.status == b.status == "DONE"
    return a, b


@pytest.fixture
def phases(monkeypatch):
    """Every ``_RequestPhase`` the two engines make, in order."""
    made = []

    class Recorded(engine_mod._RequestPhase):
        def __init__(self, name, handle, **kw):
            super().__init__(name, handle, **kw)
            made.append((name, self))

    monkeypatch.setattr(engine_mod, "_RequestPhase", Recorded)
    monkeypatch.setattr(paged_mod, "_RequestPhase", Recorded)
    return made


@pytest.mark.parametrize("cls,kw", ENGINES)
def test_admit_hold_is_the_host_gap_of_an_admission_beside_rows(
        net, cls, kw):
    eng = _engine(net, cls, **kw)
    m = eng.metrics
    a = eng.submit(np.arange(1, 6)[None], 8)
    for _ in range(3):
        eng.step()
    # the first admission found no row resident, and no iteration since
    # admitted: host gaps (from the first token's read on), no hold
    assert m.host_gap.count == 2 and m.admit_hold.count == 0
    b = eng.submit(np.arange(1, 8)[None], 4)
    gaps = m.host_gap.sum
    reads = m.read_wait.count
    eng._admit()
    # the admission's launches lie inside the gap, and no read does
    assert m.read_wait.count == reads and m.prefill.count == 1
    assert m.host_gap.count == 2
    eng._decode_once()
    assert eng.active_slots == 2
    assert m.admit_hold.count == 1 and m.host_gap.count == 3
    assert m.admit_hold.sum == m.host_gap.sum - gaps
    # longer than an ordinary step's gap: the admission's clock reads
    # lie inside it
    assert m.admit_hold.sum > gaps / 2
    # the gap ended at the launch; the step in flight and the first
    # token were read after it, and the next gap starts at the latter
    assert m.read_wait.count == reads + 1 and m.prefill.count == 2
    assert eng._read_done == b.first_token_time
    eng.run_until_idle()
    assert a.status == b.status == "DONE"
    assert m.admit_hold.count == 1 <= m.admitted.value
    assert m.host_gap.count > 3
    eng.close()


@pytest.mark.parametrize("cls,kw", ENGINES)
def test_an_idle_engine_adds_no_sample(net, cls, kw):
    eng = _engine(net, cls, **kw)
    m = eng.metrics
    for _ in range(3):
        eng.step()
    assert m.host_gap.count == m.admit_hold.count == m.read_wait.count == 0
    _busy_run(eng)
    before = (m.host_gap.count, m.admit_hold.count, m.read_wait.count)
    assert before[1] == 1
    for _ in range(3):
        eng.step()
    assert before == (m.host_gap.count, m.admit_hold.count,
                      m.read_wait.count)
    eng.close()


def test_an_admission_into_an_empty_engine_holds_nobody(net):
    eng = _engine(net)
    for _ in range(2):
        h = eng.submit(np.arange(1, 6)[None], 3)
        eng.run_until_idle()
        assert h.status == "DONE"
    assert eng.metrics.admitted.value == 2
    assert eng.metrics.admit_hold.count == 0
    eng.close()


def test_report_render_and_scrape_carry_admit_hold(net):
    eng = _engine(net)
    _busy_run(eng)
    rep = eng.metrics.report()
    assert rep["admit_hold"]["count"] == 1
    assert rep["admit_hold"]["sum"] == eng.metrics.admit_hold.sum > 0
    assert "admit_hold" in eng.metrics.render()
    from paddle_tpu.observability.exporter import prometheus_text

    assert "paddle_serving_admit_hold_seconds_count 1" in prometheus_text()
    eng.close()


def test_a_fresh_metrics_object_takes_the_next_samples(net):
    """``serve_bench`` swaps ``engine.metrics`` after warm-up: the new
    histogram follows the engine's current object."""
    eng = _engine(net)
    _busy_run(eng)
    old, eng.metrics = eng.metrics, ServingMetrics()
    _busy_run(eng)
    assert old.admit_hold.count == eng.metrics.admit_hold.count == 1
    eng.close()


@pytest.mark.parametrize("cls,kw", ENGINES)
def test_every_read_is_a_span_and_a_read_wait_sample(net, cls, kw):
    eng = _engine(net, cls, **kw)
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    try:
        _busy_run(eng)
        host = {k: len(v) for k, v in profiler._HOST_TIMES.items()}
    finally:
        prof.stop()
    m = eng.metrics
    assert host["serving::read"] == m.read_wait.count > 0
    # a step is read at most once (a row's end may drop the last one
    # unread), under the next launch's span; nothing is read for an
    # admission, whose first token has a read of its own
    assert host["serving::read"] <= m.resident_tokens.count \
        <= host["serving::decode_step"]
    assert "serving::settle" not in host and m.admit_hold.count == 1
    assert host["serving::first_token"] == m.prefill.count \
        == m.admitted.value == 2
    # one clock read on either side of the span: a tick a read
    assert m.read_wait.sum == m.read_wait.count * eng.clock.tick
    # what varies is no part of a name
    assert not [k for k in host if k.startswith("serving::")
                and any(c.isdigit() for c in k)]
    eng.close()


def test_request_spans_come_from_the_phase_calls(net, phases):
    """A warm prefix hit through the paged engine: ``engine.gather``
    under ``engine.prefill`` with its pages, ``engine.adopt`` under the
    request with its bucket, each finished by the phase of the same
    interval, whose profiler span carries the request's id."""
    tr = Tracer(process="test", sample=1)
    prev = set_tracer(tr)
    try:
        eng = _engine(net, PagedServingEngine, page_size=8,
                      prefix_cache=True)
        prompt = np.arange(1, 21)[None]
        handles = []
        for _ in range(2):
            h = eng.submit(prompt, 3)
            h.trace = tr.start_trace("frontend.request")
            handles.append(h)
            eng.run_until_idle()
        assert all(h.status == "DONE" for h in handles)
    finally:
        set_tracer(prev)
    assert [n for n, _ in phases] \
        == ["prefill", "adopt", "gather", "chunk_prefill", "adopt"]
    warm = {s["name"]: s for s in tr.buffer.get(handles[1].trace.trace_id)}
    assert warm["engine.prefill"]["attrs"]["mode"] == "chunk"
    assert warm["engine.gather"]["parent_id"] \
        == warm["engine.prefill"]["span_id"]
    assert warm["engine.gather"]["attrs"] == {"pages": 3}
    assert warm["engine.adopt"]["parent_id"] == handles[1].trace.span_id
    assert warm["engine.adopt"]["attrs"] == {"bucket": 32}
    cold = {s["name"] for s in tr.buffer.get(handles[0].trace.trace_id)}
    assert "engine.gather" not in cold and "engine.adopt" in cold
    # the profiler spans of the same calls name the request
    for (name, ph), h in zip(phases, [handles[0]] * 2 + [handles[1]] * 3):
        assert ph.name == f"serving::{name}"
        assert ph._attrs["rid"] == h.request.request_id, name
        assert ph._attrs["bucket"] == 32, name
    assert phases[3][1]._attrs["tail"] == 8
    eng.close()


def test_a_request_that_is_sampled_out_gets_no_span_of_its_own(net, phases):
    tr = Tracer(process="test", sample=1)
    prev = set_tracer(tr)
    try:
        eng = _engine(net)
        h = eng.submit(np.arange(1, 6)[None], 3)
        assert h.trace is None
        eng.run_until_idle()
    finally:
        set_tracer(prev)
    assert h.status == "DONE"
    assert [(n, ph._span) for n, ph in phases] \
        == [("prefill", None), ("adopt", None)]
    assert phases[0][1]._attrs == {"rid": h.request.request_id, "bucket": 8}
    eng.close()


def test_a_phase_that_raises_closes_its_spans(net):
    tr = Tracer(process="test", sample=1)
    prev = set_tracer(tr)
    eng = _engine(net)
    try:
        h = eng.submit(np.arange(1, 6)[None], 3)
        h.trace = tr.start_trace("frontend.request")

        def broken(*a, **k):
            raise RuntimeError("no device")

        eng._run = broken
        with pytest.raises(RuntimeError, match="no device"):
            eng.step()
    finally:
        set_tracer(prev)
    assert h.status == "REJECTED"
    span, = [s for s in tr.buffer.get(h.trace.trace_id)
             if s["name"] == "engine.prefill"]
    assert span["attrs"] == {"mode": "local", "bucket": 8,
                             "error": "admission_error"}
    del eng._run
    eng.close()
