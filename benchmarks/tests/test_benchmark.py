"""The benchmark's own tests. Run by hand, on the CPU, from the root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite (``tests/``).
"""
from __future__ import annotations

import glob
import json
import os
import sys

# the hybrid rehearsal needs four (virtual) devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, loadgen, peaks  # noqa: E402
from benchmarks.readers import (  # noqa: E402
    device_trace, engine_report, generator, step_clock)


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ manifest
def test_manifest_and_files_agree():
    """Every name in BENCHMARK.json finds its files, the cell's own
    lists of metrics match the manifest's ``workloads`` keys, and each
    layer metric moves a metric its cells report."""
    m = _json("BENCHMARK.json")
    e2e = {x["name"]: x for x in m["end_to_end"]}
    per = {x["name"]: x for x in m["per_layer"]}
    cfgs = {c["name"]: c for c in m["configs"]}
    assert "setup_s" in e2e
    for w in m["workloads"]:
        cell = _json("benchmarks", "workloads", f"{w['name']}.json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        cfg = _json(cfgs[w["config"]]["file"])
        assert set(cfg["reduced"]) == set(cfgs[w["config"]]["reduced"])
        loadgen.load_mix(os.path.join(
            ROOT, "benchmarks", "traffic", f"{w['traffic']}.json"))
        assert len(w["why"]) <= 200
        for name in cell["end_to_end"]:
            assert w["name"] in e2e[name].get("workloads", [w["name"]]), name
        for name in cell["per_layer"]:
            spec = _json("benchmarks", "layer_metrics", f"{name}.json")
            assert w["name"] in per[name].get("workloads", [w["name"]]), name
            assert (spec["unit"], spec["layer"], spec["moves"]) == \
                (per[name]["unit"], per[name]["layer"], per[name]["moves"])
            assert spec["moves"] in cell["end_to_end"], (w["name"], name)
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", "readers",
                f"{spec['source']['reader']}.py"))
    for name, x in list(e2e.items()) + list(per.items()):
        for cellname in x.get("workloads", []):
            cell = _json("benchmarks", "workloads", f"{cellname}.json")
            assert name in cell["end_to_end"] + cell["per_layer"]


# ------------------------------------------------------------- traffic
@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    ROOT, "benchmarks", "traffic", "*.json"))), ids=os.path.basename)
def test_traffic_is_deterministic_in_seed(path):
    mix = loadgen.load_mix(path)
    if mix["kind"] == "train_batches":
        small = {**mix, "pool": 2, "seq": 8}
        a = np.asarray(loadgen.plan_train_batches(small, 7, 100))
        b = np.asarray(loadgen.plan_train_batches(small, 7, 100))
        c = np.asarray(loadgen.plan_train_batches(small, 8, 100))
        assert (a == b).all() and (a != c).any() and a.shape == (2, mix["batch"], 8)
        return

    def plan(seed):
        if mix["kind"] == "closed_loop":
            return [r for c in loadgen.plan_closed_loop(mix, seed, 1000)
                    for r in c]
        return loadgen.plan_open_loop(mix, seed, 1000, 30)

    a, b, c = plan(2**31 + 11), plan(2**31 + 11), plan(5)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # another seed: the same multiset of prompt lengths, another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    d = loadgen.describe([len(r.prompt) for r in a])
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert lo <= d["min"] <= d["median"] <= d["max"] <= hi
    if mix["kind"] == "open_loop":
        due = [r.due for r in a]
        assert due == sorted(due) and due[0] == 0.0
        n = len(a)
        assert abs(n - mix["rate_per_s"] * (30 + mix.get("ramp_s", 0))) <= 1
        # every seed: the same fixed set of gaps, in another order
        fixed = set(loadgen.arrival_gaps(mix, n).round(9))
        assert set(np.diff(due).round(9)) <= fixed
        assert set(np.diff([r.due for r in c]).round(9)) <= fixed


def test_lognormal_quantiles_and_shared_prefix():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8, "lo": 64,
            "hi": 2048}
    x = loadgen.quantile_lengths(spec, 1001)
    assert x.min() == 64 and x.max() == 2048 and abs(np.median(x) - 512) <= 1
    mix = {"kind": "open_loop", "rate_per_s": 2.0, "prompt_len": spec,
           "output_len": {"dist": "fixed", "value": 4},
           "shared_prefix": {"tokens": 32, "groups": 2}}
    reqs = loadgen.plan_open_loop(mix, 3, 1000, 10)
    assert reqs[0].prompt[:32].tolist() == reqs[2].prompt[:32].tolist()
    assert reqs[0].prompt[:32].tolist() != reqs[1].prompt[:32].tolist()


def test_window_measures_counts_from_due_time():
    r = loadgen.Request(0, 1.0, np.arange(5), 3)
    r.sent, r.status, r.times = 101.25, "DONE", [102.0, 102.5, 103.5]
    late = loadgen.Request(1, 2.0, np.arange(5), 3)
    late.sent, late.status = 102.0, "rejected:503"
    out = loadgen.Request(2, 50.0, np.arange(5), 3)      # due after the cut
    m = loadgen.window_measures([r, late, out], 100.0, 103.0, t0=100.0)
    assert m["attempted"] == 2 and m["failed"] == 1
    assert m["tokens"] == 2 and m["gaps"] == [0.5]
    assert m["ttft"] == [1.0] and m["late"] == [0.25, 0.0]
    assert loadgen.resident_tokens([r], 102.6) == 5 + 2
    assert loadgen.resident_tokens([r], 104.0) == 0
    assert loadgen.percentile(list(range(1, 101)), 95) == 95


# --------------------------------------------------------------- flops
def test_flops_against_hand_counts():
    m3 = _json("benchmarks", "configs", "mistral-7b-v0.3-d3.json")
    assert flops.layer_matmul_params(m3) == 218_103_808
    assert flops.matmul_params(m3) == 3 * 218_103_808 + 4096 * 32768
    assert round(flops.matmul_params(m3) / 1e6) == 789     # the issue's 788 M
    per_tok = flops.train_flops_per_token(m3, 2048)
    assert per_tok == 6 * flops.matmul_params(m3) + 6 * 3 * 4096 * 2048
    i8 = _json("benchmarks", "configs", "internlm2-7b-d8.json")
    weights = (8 * 218_103_808 + 4096 * 92544) * 2
    assert flops.decode_bytes_per_step(i8, 0) == weights
    # one resident token: K and V, 8 layers, 8 KV heads x 128, bf16
    assert flops.decode_bytes_per_step(i8, 1) - weights == 2 * 8 * 8 * 128 * 2


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


# ------------------------------------------------------------- readers
def test_device_trace_reducer_on_a_recorded_trace():
    """A small trace kept as a text proto: one TPU plane with two runs
    of a program, overlapping and nested ops and a gap; a host plane
    that must not count."""
    from jax.profiler import ProfileData

    with open(os.path.join(os.path.dirname(__file__), "small_trace.pbtxt")) as f:
        prof = ProfileData.from_text_proto(f.read())
    chips = device_trace.reduce_planes(device_trace.planes_of(prof))
    assert [c["plane"] for c in chips] == ["/device:TPU:0"]
    c = chips[0]
    # ops: [0,4) [2,6) nested [3,4) | gap 6..10 | [10,13) ms -> 9 ms busy
    assert c["busy_s"] == pytest.approx(9e-3)
    assert c["modules"]["jit_step"] == [2, pytest.approx(9.5e-3)]
    assert c["gaps"] == {"jit_step->jit_step": pytest.approx(3.5e-3)}
    assert c["ops"]["fusion.1"] == pytest.approx(7e-3)
    tr = device_trace.summarize(chips, window_s=0.015)
    assert tr["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(7e-3)]
    obs = {"trace": tr, "peaks": {"flops_bf16": 1e12},
           "work": {"train_flops_per_step": 1e9}}
    assert device_trace.read({"quantity": "idle_share"}, obs) == \
        pytest.approx(40.0)
    # 1e9 flop / 1e12 flop/s = 1 ms least; 4.75 ms a run
    assert device_trace.read(
        {"quantity": "roofline_share", "work": "train_flops_per_step",
         "peak": "flops_bf16", "program": "jit_step"}, obs) == \
        pytest.approx(100 / 4.75)
    assert device_trace.read({"quantity": "idle_share"}, {}) is None


def test_small_readers():
    rep = lambda c, s: {"itl": {"count": c, "sum": s},
                        "slot_occupancy": {"count": c, "sum": s * 100}}
    obs = {"engine_report": (rep(10, 1.0), rep(30, 2.0)),
           "engine": {"max_batch_size": 32}}
    assert engine_report.read({"histogram": "itl", "scale": 1e3}, obs) == \
        pytest.approx(50.0)
    assert engine_report.read({"histogram": "slot_occupancy",
                               "share_of": "max_batch_size"}, obs) == \
        pytest.approx(100 * 5.0 / 32)
    assert engine_report.read({"histogram": "ttft"}, obs) is None
    assert step_clock.read({}, {"step_clock": [(10, 1.6), (10, 1.7), (10, 1.8)]}) \
        == pytest.approx(170.0)
    assert step_clock.read({}, {}) is None
    assert generator.read({"quantity": "late_p95", "scale": 1e3},
                          {"generator_late": [0.001] * 99 + [0.5]}) == \
        pytest.approx(1.0)


# ---------------------------------------------------------- rehearsals
TOY = {"builder": "dense_decoder", "hidden_size": 64, "intermediate_size": 128,
       "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "vocab_size": 512,
       "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
       "rope_theta": 1e6, "tie_word_embeddings": False}
TOY_ENGINE = {"max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
              "min_bucket": 16, "cache_dtype": "bfloat16"}
TOY_MANIFEST = {"end_to_end": [
    {"name": "train_tok_s", "unit": "tokens/s"},
    {"name": "serve_tok_s", "unit": "tokens/s"},
    {"name": "itl_p95_ms", "unit": "ms"}, {"name": "ttft_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"}]}
TOY_CELLS = {
    "train": (
        {"job": "train", "trainer": {
            "optimizer": "AdamW", "learning_rate": 1e-4, "amp_level": "O2",
            "amp_dtype": "bfloat16", "read_every": 2, "warm_steps": 2},
         "end_to_end": ["train_tok_s", "setup_s"],
         "per_layer": ["step_ms.train", "train_step_roofline",
                       "device_idle.train"]},
        {"kind": "train_batches", "batch": 2, "seq": 32, "pool": 8}),
    "hybrid": (
        {"job": "train", "parallel": {"dp": 2, "mp": 2}, "trainer": {
            "optimizer": "AdamW", "learning_rate": 1e-4, "amp_level": "O2",
            "amp_dtype": "bfloat16", "read_every": 2, "warm_steps": 2},
         "end_to_end": ["train_tok_s", "setup_s"],
         "per_layer": ["step_ms.train", "train_step_roofline",
                       "device_idle.train"]},
        {"kind": "train_batches", "batch": 4, "seq": 32, "pool": 8}),
    "closed": (
        {"job": "serve", "engine": TOY_ENGINE,
         "check": {"prompt_lens": [8, 12, 16, 20], "max_new": 4, "pad_to": 32},
         "trace_seconds": 0.5,
         "end_to_end": ["serve_tok_s", "itl_p95_ms", "setup_s"],
         "per_layer": ["slot_occupancy.serve", "decode_step_ms.serve",
                       "decode_step_roofline", "device_idle.serve"]},
        {"kind": "closed_loop", "clients": 4, "requests_per_client": 50,
         "prompt_len": {"dist": "uniform", "lo": 8, "hi": 24},
         "output_len": {"dist": "uniform", "lo": 8, "hi": 24}}),
    "open": (
        {"job": "serve", "engine": TOY_ENGINE,
         "check": {"prompt_lens": [8, 12, 16, 20], "max_new": 4, "pad_to": 32},
         "trace_seconds": 0.5,
         "end_to_end": ["ttft_p95_ms", "itl_p95_ms", "serve_tok_s", "setup_s"],
         "per_layer": ["gen_late_ms.serve", "queue_wait_ms.serve",
                       "slot_occupancy.serve", "decode_step_ms.serve",
                       "decode_step_roofline", "device_idle.serve"]},
        {"kind": "open_loop", "rate_per_s": 8.0, "ramp_s": 1, "streams": 16,
         "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                        "lo": 4, "hi": 32},
         "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "lo": 2, "hi": 16}}),
}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_rehearsal_prints_the_contract_keys(cell, trace, monkeypatch):
    """Each job end to end on the CPU at a toy size: the last line's
    keys, and that each metric the cell lists is a finite number. No
    number from here is a measurement."""
    import jax

    from benchmarks import run

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    spec, mix = TOY_CELLS[cell]
    chips = 4 if "parallel" in spec else 1
    if len(jax.devices()) < chips:
        pytest.skip("needs --xla_force_host_platform_device_count=4")
    files = (TOY_MANIFEST, {"chips": chips}, spec, TOY, mix)
    try:
        out = run.measure(f"toy-{cell}", 2**31 + 5, 2.0, trace, files,
                          jax.devices()[:chips])
    finally:
        from paddle_tpu.parallel import mesh as mesh_mod

        mesh_mod.set_mesh(None)     # a hybrid cell installs a mesh
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["attempted"] > 0 and out["failed"] == 0
    want = spec["per_layer"] if trace else spec["end_to_end"]
    # the CPU writes no device plane: trace-derived metrics are left out
    want = [n for n in want if "roofline" not in n and "device_idle" not in n]
    assert set(want) <= set(out["metrics"]), out["metrics"]
    for name, m in out["metrics"].items():
        assert np.isfinite(m["value"]) and m["unit"], name
    json.dumps(out)
