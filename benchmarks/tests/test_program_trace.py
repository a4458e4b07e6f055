"""Tests of the reader ``program_trace`` on a small recorded trace
(``program_trace.pbtxt``), by hand like the benchmark's other tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

The trace, in milliseconds: two runs of ``jit__decode_body`` at [0, 4)
and [10, 14), so the device idles for [4, 10). A run holds ``%fusion.1``
[0, 2) under ``attn_core`` with ``%rope.4`` [0.5, 1.5) nested in it
(scope through a ``ref_value``, inside ``transpose(jvp(...))``),
``%attn_core_fusion.2`` [2, 3) under ``lm_head`` (only NAMED like a
scope) and ``%add.3`` [3, 4) under ``attn_core_like``. The driver
thread holds ``serving::step`` [3.5, 9) around ``serving::emit`` [5, 7),
then ``frontend::lock_wait`` [9, 9.5), and a Python-tracer event over
everything; another thread holds a ``serving::`` span over the whole
gap, [4, 10), which is not the driver's.
"""
from __future__ import annotations

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.readers import device_trace, program_trace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = (
    "host_gap_ms.serve", "submit_wait_ms.serve", "prefill_ms.serve",
    "idle_attributed.serve", "attn_core_ms.serve", "optimizer_ms.train",
    "lm_head_loss_ms.train")


def _reduced(name):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, name)) as f:
        data = ProfileData.text_proto_to_serialized_xspace(f.read())
    planes = device_trace.planes_of(ProfileData.from_serialized_xspace(data))
    return program_trace.reduce_trace(planes, program_trace.op_scopes(data))


@pytest.fixture(scope="module")
def obs():
    return {"trace": {"busy_s": 0.008, "window_s": 0.015},
            "program_trace": _reduced("program_trace.pbtxt")}


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_attribution_sums_to_the_idle_time(obs):
    chip, = obs["program_trace"]["chips"]
    assert chip["idle_s"] == pytest.approx(6e-3)
    assert sum(chip["by_span"].values()) == pytest.approx(chip["idle_s"])
    # each part to the INNERMOST span over it, on the line that holds
    # serving::step alone; the step's own time is [4, 5) and [7, 9)
    assert chip["by_span"] == {
        program_trace.STEP_OWN: pytest.approx(3e-3),
        "serving::emit": pytest.approx(2e-3),
        "frontend::lock_wait": pytest.approx(0.5e-3),
        program_trace.UNATTRIBUTED: pytest.approx(0.5e-3)}


def test_unattributed_is_what_no_phase_covers(obs):
    # the span of the whole iteration is no phase: a loop that spent
    # all its time outside emit and lock_wait would read 0
    got = program_trace.read(_spec("idle_attributed.serve")["source"], obs)
    assert got == pytest.approx(100.0 * (2.0 + 0.5) / 6.0)
    assert 0.0 <= got <= 100.0
    # no span at all: everything is unattributed
    assert program_trace.attribute([(0, 10)], []) == {
        program_trace.UNATTRIBUTED: pytest.approx(10e-9)}
    # no span at all: everything is unattributed
    assert program_trace.attribute([(0, 10)], []) == {
        program_trace.UNATTRIBUTED: pytest.approx(10e-9)}


def test_scope_time_matches_a_path_component(obs):
    spec = _spec("attn_core_ms.serve")["source"]
    # %fusion.1 two ms a run; the nested %rope.4 counts once; neither
    # %attn_core_fusion.2 (its own NAME) nor attn_core_like (a longer
    # component) is attn_core
    assert program_trace.read(spec, obs) == pytest.approx(2.0)
    head = dict(spec, scopes=["lm_head"])
    assert program_trace.read(head, obs) == pytest.approx(1.0)
    assert program_trace.read(dict(spec, scopes=["attn_core", "lm_head"]),
                              obs) == pytest.approx(3.0)
    # no such program, no such scope: nothing, and no error
    assert program_trace.read(dict(spec, program="jit_step"), obs) is None
    assert program_trace.read(dict(spec, scopes=["optimizer"]), obs) is None
    rec = obs["program_trace"]["chips"][0]["programs"]["jit__decode_body"]
    by = program_trace.seconds_by(rec, program_trace._group)
    assert sum(by.values()) <= rec["run_s"] / rec["runs"] + 1e-12


@pytest.mark.parametrize("path,want", [
    ("jit(_decode_body)/model/1/self_attn/attn_core/broadcast_in_dim",
     ["model", "1", "self_attn", "attn_core"]),
    ("jit(step)/transpose(jvp(model))/0/self_attn/attn_core/add_any",
     ["model", "0", "self_attn", "attn_core"]),
    ("jit(step)/jvp(loss)/jit(take_along_axis)/gather", ["loss"]),
    ("jit(step)/transpose(jvp(lm_head))/dot_general", ["lm_head"]),
    ("jit(step)/optimizer/mul", ["optimizer"]),
    ("", []),
])
def test_scope_path_components(path, want):
    assert program_trace.components(path) == want


def test_self_segments_name_the_innermost_span():
    spans = [(0, 100, "outer"), (10, 30, "a"), (15, 20, "aa"),
             (40, 50, "b"), (120, 130, "later")]
    assert program_trace.self_segments(spans) == [
        (0, 10, "outer"), (10, 15, "a"), (15, 20, "aa"), (20, 30, "a"),
        (30, 40, "outer"), (40, 50, "b"), (50, 100, "outer"),
        (120, 130, "later")]
    assert program_trace.self_segments([]) == []


def test_a_program_without_spans_or_scopes_reads_as_nothing():
    """The parent of PR 26: ``small_trace.pbtxt`` has no phase span and
    no ``tf_op``; a CPU run has no device plane at all."""
    tr = _reduced("small_trace.pbtxt")
    obs = {"trace": {"busy_s": 0.009}, "program_trace": tr}
    assert tr["chips"][0]["by_span"] == {}
    for name in NEW_METRICS:
        spec = _spec(name)
        if spec["source"]["reader"] == "program_trace":
            assert program_trace.read(spec["source"], obs) is None, name
    assert program_trace.read(
        _spec("attn_core_ms.serve")["source"], {"trace": None}) is None
    assert program_trace.reduce_trace(
        [("/host:CPU", [("python3", [("serving::step", 0, 5)])])], {}) is None


def test_new_metric_files_are_whole():
    """What a ``benchmark`` PR needs to list them: the manifest's keys,
    a reader that exists, a layer and an end-to-end metric the
    manifest knows or that the file states."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for name in NEW_METRICS:
        spec = _spec(name)
        assert spec["name"] == name
        assert spec["moves"] in e2e
        assert spec["better"] in ("lower", "higher")
        assert spec["source_kind"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "readers",
            f"{spec['source']['reader']}.py"))
        assert name.endswith(".train") == (spec["moves"] == "train_tok_s")
    assert len(glob.glob(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "*.json"))) >= 9 + len(
            NEW_METRICS)


def test_histogram_metrics_read_the_engine_report():
    from benchmarks.readers import engine_report

    rep = lambda n, s: {"host_gap": {"count": n, "sum": s},
                        "prefill": {"count": n, "sum": 2 * s},
                        "submit_wait": {"count": n, "sum": 3 * s}}
    obs = {"engine_report": (rep(10, 0.1), rep(110, 2.1))}
    for name, ms in (("host_gap_ms.serve", 20.0), ("prefill_ms.serve", 40.0),
                     ("submit_wait_ms.serve", 60.0)):
        assert engine_report.read(_spec(name)["source"], obs) == \
            pytest.approx(ms)
    # the parent's report has no such histogram: nothing, no error
    old = {"engine_report": ({"itl": {"count": 1, "sum": 1.0}},) * 2}
    assert engine_report.read(_spec("host_gap_ms.serve")["source"],
                              old) is None


@pytest.mark.parametrize("cell,extra", [
    ("closed", [n for n in NEW_METRICS if n.endswith(".serve")]),
    ("train", [n for n in NEW_METRICS if n.endswith(".train")]),
])
def test_rehearsal_with_the_new_metrics_listed(cell, extra, monkeypatch):
    """A toy cell on the CPU whose ``per_layer`` lists the new metrics,
    as a ``benchmark`` PR would list them in ``workloads/<cell>.json``:
    the three histograms read as numbers; the CPU writes no device
    plane, so what is read from one is left out and nothing raises."""
    import jax
    import numpy as np
    from test_benchmark import TOY, TOY_CELLS, TOY_MANIFEST

    from benchmarks import peaks, run

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    spec, mix = TOY_CELLS[cell]
    spec = dict(spec, per_layer=spec["per_layer"] + extra)
    out = run.measure(f"toy-{cell}", 2**31 + 7, 2.0, 1,
                      (TOY_MANIFEST, {"chips": 1}, spec, TOY, mix),
                      jax.devices()[:1])
    assert out["correct"] and out["failed"] == 0
    got = {n for n in extra if n in out["metrics"]}
    assert got == {n for n in extra
                   if _spec(n)["source"]["reader"] == "engine_report"}
    for n in got:
        assert np.isfinite(out["metrics"][n]["value"])
        assert out["metrics"][n]["unit"] == "ms"
