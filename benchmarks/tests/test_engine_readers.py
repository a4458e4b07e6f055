"""Readers ``engine_ratio`` and ``program_ms`` on hand-made observations,
the metric files that use them, and ``tools/cell_metrics.py`` rehearsed
on the CPU at a toy size (no number from there is a measurement).

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import peaks  # noqa: E402
from benchmarks.readers import engine_ratio, program_ms  # noqa: E402


def _rep(overlapped, launches, gap, hold):
    return {"counters": {"steps_overlapped": overlapped},
            "resident_tokens": {"count": launches, "sum": 1e6 * launches},
            "host_gap": {"count": launches, "sum": gap},
            "admit_hold": {"count": hold, "sum": 0.1 * hold}}


OBS = {"engine_report": (_rep(5, 10, 2.0, 1), _rep(95, 110, 6.0, 4))}


def test_counter_growth_over_a_histograms_count():
    spec = {"numerator": {"counter": "steps_overlapped"},
            "denominator": {"histogram": "resident_tokens",
                            "field": "count"}, "scale": 100.0}
    assert engine_ratio.read(spec, OBS) == pytest.approx(90.0)


def test_one_histograms_sum_over_anothers():
    spec = {"numerator": {"histogram": "admit_hold", "field": "sum"},
            "denominator": {"histogram": "host_gap", "field": "sum"},
            "scale": 100.0}
    assert engine_ratio.read(spec, OBS) == pytest.approx(100 * 0.3 / 4)
    del spec["scale"]
    assert engine_ratio.read(spec, OBS) == pytest.approx(0.3 / 4)


def test_a_denominator_that_did_not_grow_gives_nothing():
    spec = {"numerator": {"counter": "steps_overlapped"},
            "denominator": {"histogram": "no_such", "field": "sum"}}
    assert engine_ratio.read(spec, OBS) is None       # a parent's report
    still = {"engine_report": (OBS["engine_report"][1],) * 2}
    spec["denominator"] = {"histogram": "resident_tokens", "field": "count"}
    assert engine_ratio.read(spec, still) is None
    assert engine_ratio.read(spec, {}) is None


def test_program_ms_is_the_mean_over_every_matching_program():
    obs = {"trace": {"modules": {
        "jit_prefill_body": [3.0, 0.330], "jit_chunk_prefill_body": [1.0, 0.030],
        "jit__decode_body": [100.0, 2.0], "jit_adopt_body": [4.0, 0.001]}}}
    assert program_ms.read({"program": "prefill_"}, obs) \
        == pytest.approx(1e3 * 0.360 / 4)
    assert program_ms.read({"program": "prefill_state"}, obs) is None
    assert program_ms.read({"program": "prefill_"}, {"trace": None}) is None
    assert program_ms.read({"program": "prefill_"}, {}) is None


def test_the_new_metric_files_read_what_the_program_reports():
    """Each new file names a reader that is there and a histogram or
    counter that ``ServingMetrics.report()`` has."""
    from paddle_tpu.serving import ServingMetrics

    rep = ServingMetrics().report()

    def has(q):
        return q["counter"] in rep["counters"] if "counter" in q \
            else q["histogram"] in rep

    for name in ("admit_hold_ms.serve", "read_wait_ms.serve",
                 "span_tokens.serve", "steps_overlapped.serve",
                 "prefill_program_ms.serve"):
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["name"] == name
        src = spec["source"]
        if src["reader"] == "engine_report":
            assert has(src), name
        elif src["reader"] == "engine_ratio":
            assert has(src["numerator"]) and has(src["denominator"]), name
        else:
            assert src == {"reader": "program_ms", "program": "prefill_"}


def test_cell_metrics_rehearsal(monkeypatch):
    """The tool end to end over a toy closed-loop cell: the cell's own
    metrics as ``run.measure`` gives them and, beside them, every
    unlisted one whose reader finds something on a CPU; no benchmark
    file is touched."""
    import jax

    from test_benchmark import TOY, TOY_CELLS, TOY_MANIFEST
    from tools import cell_metrics

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    spec, mix = TOY_CELLS["closed"]
    listed = list(spec["per_layer"])
    out = cell_metrics.measure(
        "toy-closed", 2**31 + 7, 2.0, None,
        (TOY_MANIFEST, {"chips": 1}, spec, TOY, mix), jax.devices()[:1])
    assert spec["per_layer"] == listed
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    assert "serve_tok_s" not in got
    for name in ("read_wait_ms.serve", "span_tokens.serve",
                 "steps_overlapped.serve", "admit_hold_ms.serve",
                 "host_gap_ms.serve"):
        assert np.isfinite(got[name]["value"]), name
    assert 0 < got["steps_overlapped.serve"]["value"] <= 100
    assert got["admit_hold_ms.serve"]["value"] \
        > got["host_gap_ms.serve"]["value"]
    # the CPU writes no device plane: nothing trace-derived is read
    assert "prefill_program_ms.serve" not in got
    assert "step_ms.train" not in got
    json.dumps(out)


def test_cell_metrics_refuses_an_unknown_metric_before_the_run():
    from tools import cell_metrics

    cell = {"per_layer": [], "job": "serve"}
    with pytest.raises(FileNotFoundError):
        cell_metrics.measure("x", 1, 1.0, ["no_such_metric"],
                             ({}, {}, cell, {}, {}), [])
    assert set(cell_metrics.unlisted({"per_layer": ["host_gap_ms.serve"]})) \
        >= {"admit_hold_ms.serve", "prefill_program_ms.serve"}
    assert "host_gap_ms.serve" not in cell_metrics.unlisted(
        {"per_layer": ["host_gap_ms.serve"]})
