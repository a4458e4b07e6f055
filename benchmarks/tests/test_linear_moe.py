"""The linear-attention expert decoder's part of the benchmark: its
count module against the program's own parameter shapes and hand-worked
sizes, the job wrapper's work and verdict, the new metric files through
their readers on a recorded trace, and a toy rehearsal of the cell's
job through ``run.measure``. Run by hand with the benchmark's other
tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import linear_moe_counts as counts, peaks  # noqa: E402
from benchmarks.jobs import serve_linear_moe as job  # noqa: E402
from benchmarks.readers import (  # noqa: E402
    device_trace,
    engine_report,
    program_trace,
    scope_ops,
)

CELL = "solar-open2-serve-reason-batch64"
CONFIG = "solar-open2-250b-ep8-d4"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json("benchmarks", "configs", f"{CONFIG}.json")


# -------------------------------------------------------------- counts
def test_counts_agree_with_the_programs_own_shapes(cfg):
    """To the parameter: the program's parameters for this file, built
    under LazyGuard (shapes only), against the counts file, whole and
    by kind of layer."""
    from benchmarks.models import linear_moe_decoder as builder

    shapes = builder.parameter_shapes(cfg)
    size = lambda pre: sum(int(np.prod(s)) for k, s in shapes.items()
                           if k.startswith(pre))
    assert size("") == counts.model_params(cfg) == 3_308_376_640
    assert size("model.layers.0.mixer.") == counts.gqa_params(cfg) \
        == 109_051_904
    for i in (1, 2, 3):
        assert size(f"model.layers.{i}.mixer.") == counts.kda_params(cfg) \
            == 137_740_480
    assert shapes["model.layers.2.mlp.gate_weight"] == (4096, 320)
    assert shapes["model.layers.2.mlp.experts_gate_up"] == (40, 4096, 2560)
    assert counts.expert_params(cfg) == 3 * 4096 * 1280
    for i in range(4):
        assert size(f"model.layers.{i}.") == counts.layer_params(cfg, i)
    assert shapes["lm_head.weight"] == (4096, 24576)
    # 6.62 GB in bf16, as the configuration's file says
    assert round(2 * counts.model_params(cfg) / 1e9, 2) == 6.62
    assert "3 308 376 640" in cfg["stands_for"]


def test_decode_bytes_from_hand_worked_shapes(cfg):
    outside = 109_051_904 + 3 * 137_740_480 + 4 * (
        2 * 4096 + 4096 * 320 + 15_728_640) + 4096 * 24576 + 4096
    state = 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert counts.row_state_bytes(cfg) == state == 13_025_280
    assert counts.kv_bytes_per_token(cfg) == 4096
    assert counts.kda_step_bytes(cfg, 64) == 2 * 64 * state
    fixed = 2 * outside + 2 * 64 * state
    assert counts.decode_bytes_per_step(cfg, 0, 0, 64) == fixed
    # the edges of experts_touched: none, and all 4 x 40 held
    assert counts.decode_bytes_per_step(cfg, 160, 0, 64) - fixed == \
        160 * 3 * 4096 * 1280 * 2
    assert counts.decode_bytes_per_step(cfg, 0, 1, 64) - fixed == 4096
    # the issue's step: 80 % of the held experts, 64 x 2.4 k tokens
    step = counts.decode_bytes_per_step(cfg, 0.8 * 160, 64 * 2400, 64)
    assert 7.6e9 < step < 7.8e9
    # one 2048 bucket: 32 chunks x 64 heads x 3 layers
    per_chunk = 4 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert counts.kda_chunk_flops(cfg, 2048) == 3 * 64 * 32 * per_chunk
    f0 = counts.decode_flops_per_step(cfg, 64, 0)
    assert counts.decode_flops_per_step(cfg, 64, 1000) - f0 == \
        4 * 1000 * 64 * 128


def test_job_work_and_readers(cfg):
    rep = lambda n, touched, resident, local: {
        "experts_touched": {"count": n, "sum": touched},
        "resident_tokens": {"count": n, "sum": resident},
        "local_assignments": {"count": n, "sum": local}}
    pair = (rep(10, 1000.0, 1e6, 2560.0),
            rep(30, 1000.0 + 20 * 128, 1e6 + 20 * 153600, 2560.0 + 20 * 256))
    engine = {"max_batch_size": 64}
    work = job.step_work(cfg, engine, 2048, 128.0, 153600.0)
    assert work["moe_experts_bytes_per_step"] == 128 * 31_457_280
    assert work["linear_moe_decode_bytes_per_step"] == \
        counts.decode_bytes_per_step(cfg, 128.0, 153600.0, 64)
    assert work["kda_step_bytes_per_step"] == 2 * 64 * 13_025_280
    # a program without the histograms: the shape-only work alone
    assert set(job.step_work(cfg, engine, 2048, None, 153600.0)) == {
        "kda_step_bytes_per_step", "kda_chunk_flops_per_prefill"}
    obs = {"engine_report": pair,
           "engine": {"routed_expert_slots": 160, "assignment_slots": 2048}}
    spec = _json("benchmarks", "layer_metrics", "local_assignments.serve.json")
    assert engine_report.read(spec["source"], obs) == pytest.approx(12.5)
    assert engine_report.read(spec["source"],
                              {"engine_report": ({}, {}), "engine": {}}) is None
    touched = _json("benchmarks", "layer_metrics",
                    "experts_touched.serve.json")
    assert engine_report.read(touched["source"], obs) == pytest.approx(80.0)
    lengths = job.path_lengths(2048, 64, 32, 8)
    at = lengths[None, :] + np.arange(32)[:, None]
    assert lengths[0] + 32 == 2048 and lengths.min() == 1512
    assert at.max() == 2047 and len(np.unique(at)) == 536


def test_the_judged_line_holds_eight_readings(capsys):
    from benchmarks.reference import linear_moe_decoder as ref

    ctx = types.SimpleNamespace(reference=ref)
    sound = {"decode_err": np.full(512, ref.PATH_ERR / 2),
             "prefill_err": np.full(64, ref.PATH_ERR / 2),
             "state_err": np.full(192, ref.PATH_STATE_ERR / 2),
             "stepped_state_err": np.full(192, ref.PATH_STATE_ERR / 2),
             "kernel_state_err": np.full(64, ref.KERNEL_STATE_ERR / 2),
             "ffn_err": np.full(4096, ref.FFN_ERR / 2),
             "route_elsewhere": 0.0, "route_decided": 4096}
    assert job.judge(ctx, sound)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["line"] == "check_path" and line["ok"] \
        and line["allowed_stepped_state_err_median"] == ref.PATH_STATE_ERR
    for key, bad in (("decode_err", np.full(512, 2 * ref.PATH_ERR)),
                     ("decode_err", np.where(np.arange(512) % 8 == 0,
                                             2 * ref.PATH_ERR_P90, 0.0)),
                     ("kernel_state_err", np.full(64, 1e-3)),
                     ("prefill_err", np.full(64, 2 * ref.PATH_ERR)),
                     ("state_err", np.where(
                         np.arange(192) % 3, 2 * ref.PATH_STATE_ERR, 0.0)),
                     ("stepped_state_err", np.full(192, 2 * ref.PATH_STATE_ERR)),
                     ("ffn_err", np.full(4096, 2 * ref.FFN_ERR)),
                     ("route_elsewhere", 2 * ref.ROUTE_ELSEWHERE),
                     ("decode_err", np.full(512, np.nan))):
        assert not job.judge(ctx, dict(sound, **{key: bad})), key


def test_new_metric_files_read_a_recorded_trace():
    """Two runs of the decode program (2 ms under ``kda_step`` in three
    layers, 1 ms elsewhere) and one of the state prefill (4 ms under
    ``kda_chunk``): each new trace metric through its own reader."""
    ms = lambda a, b, path: (int(a * 1e6), int(b * 1e6), path)
    dec = "jit(_decode_body)/model/{}/mixer/kda_step/mul"
    run = [ms(0, 1, dec.format(1)), ms(1, 1.5, dec.format(2)),
           ms(2, 2.5, dec.format(3)),
           ms(3, 4, "jit(_decode_body)/lm_head/dot_general")]
    shifted = [(a + 10**7, b + 10**7, p) for a, b, p in run]
    pre = [ms(20, 24, "jit(prefill_state_body)/model/1/mixer/kda_chunk/"
                      "while/body/dot_general"),
           ms(24, 30, "jit(prefill_state_body)/model/0/mixer/attn_core/x")]
    state = 64 * 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    obs = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1, "modules": {
               "jit__decode_body": [2.0, 0.010],
               "jit_prefill_state_body": [1.0, 0.010]}},
           "peaks": peaks.PEAKS["TPU v5 lite"],
           "work": {"kda_step_bytes_per_step": 2 * state,
                    "kda_chunk_flops_per_prefill": 197e12 * 1e-3,
                    "linear_moe_decode_bytes_per_step": 819e9 * 2e-3},
           "program_trace": {"chips": [{"by_span": {}, "idle_s": 0.0,
                                        "programs": {
               "jit__decode_body": {"runs": 2, "run_s": 0.010,
                                    "ops": run + shifted},
               "jit_prefill_state_body": {"runs": 1, "run_s": 0.010,
                                          "ops": pre}}}]}}
    spec = lambda n: _json("benchmarks", "layer_metrics", f"{n}.json")
    assert program_trace.read(spec("kda_step_ms.serve")["source"], obs) \
        == pytest.approx(2.0)
    assert scope_ops.read(spec("kda_step_roofline.serve")["source"], obs) \
        == pytest.approx(100 * (2 * state / 819e9) / 2e-3)
    assert scope_ops.read(spec("kda_chunk_roofline.serve")["source"], obs) \
        == pytest.approx(25.0)
    assert device_trace.read(
        spec("decode_step_roofline.linear_moe")["source"], obs) \
        == pytest.approx(40.0)
    # the parent: no such program, scope or work; nothing raises
    bare = dict(obs, work={}, program_trace={"chips": [{
        "by_span": {}, "idle_s": 0.0, "programs": {"jit__decode_body": {
            "runs": 1, "run_s": 0.002, "ops": run[-1:]}}}]})
    for name in ("kda_step_ms.serve", "kda_step_roofline.serve",
                 "kda_chunk_roofline.serve",
                 "decode_step_roofline.linear_moe"):
        s = spec(name)
        reader = {"program_trace": program_trace, "scope_ops": scope_ops,
                  "device_trace": device_trace}[s["source"]["reader"]]
        assert reader.read(s["source"], bare) is None, name


def test_cell_files_say_what_the_issue_asks(cfg):
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    mix = _json("benchmarks", "traffic", f"{cell['traffic']}.json")
    assert cell["traffic"] == "closed-batch-64-p2k-o1k"
    assert {k: cell["engine"][k] for k in (
        "max_batch_size", "max_seq_len", "page_size", "min_bucket",
        "cache_dtype")} == {"max_batch_size": 64, "max_seq_len": 4096,
                            "page_size": 16, "min_bucket": 128,
                            "cache_dtype": "bfloat16"}
    assert (mix["kind"], mix["clients"], mix["requests_per_client"],
            mix["stagger_first"], mix["fill_timeout_s"]) == (
        "closed_loop", 64, 24, True, 300)
    assert (mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == (1280, 2048)
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (512, 1024)
    assert cell["check"]["prompt_lens"] == [1280, 1500, 1800, 2048]
    assert max(cell["check"]["prompt_lens"]) + cell["check"]["max_new"] \
        <= cell["check"]["pad_to"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608, "max_position_embeddings": 1048576}
    assert set(cfg["reduced"]) == set(cfg["published"])
    # every key of the catalog row, no width changed
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Solar-Open2-250B"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        for key, value in row["config"].items():
            assert key in cfg, key
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    manifest = _json("BENCHMARK.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, cell["traffic"], 1)
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"])


# ----------------------------------------------------------- rehearsal
TOY = {"builder": "linear_moe_decoder", "vocab_size": 512, "hidden_size": 64,
       "intermediate_size": 128, "moe_intermediate_size": 32,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16,
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                              "num_heads": 4, "num_kv_heads": None},
       "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_rope": False,
       "use_gqa_gate": True, "kda_use_full_proj": False,
       "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
       "n_routed_experts": 4, "experts_first": 4,
       "published": {"n_routed_experts": 16}, "n_shared_experts": 1,
       "num_experts_per_tok": 4, "routed_scaling_factor": 1,
       "norm_topk_prob": True, "max_position_embeddings": 128,
       "rms_norm_eps": 1e-5, "rope_theta": 10000,
       "tie_word_embeddings": False}
TOY_MANIFEST = {"end_to_end": [
    {"name": "serve_tok_s", "unit": "tokens/s"},
    {"name": "itl_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]}
TOY_MIX = {"kind": "closed_loop", "clients": 4, "requests_per_client": 50,
           "prompt_len": {"dist": "uniform", "lo": 24, "hi": 32},
           "output_len": {"dist": "uniform", "lo": 8, "hi": 24}}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_keys(trace, monkeypatch):
    """The cell's job, builder, reference and metric files end to end
    on the CPU at a toy size (chunks of 64 over 32-token prompts: one
    padded chunk). No number from here is a measurement."""
    import jax

    from benchmarks import run

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    spec = dict(cell, param_dtype="float32", trace_seconds=0.5, engine={
        "max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
        "min_bucket": 16, "cache_dtype": "float32"},
        check={"prompt_lens": [24, 27, 30, 32], "max_new": 4, "pad_to": 48},
        path_check={"tokens": 32, "steps": 3, "stride": 2, "ffn_rows": 16})
    files = (TOY_MANIFEST, {"chips": 1}, spec, TOY, TOY_MIX)
    out = run.measure("toy-linear-moe", 2**31 + 5, 2.0, trace, files,
                      jax.devices()[:1])
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    want = cell["per_layer"] if trace else cell["end_to_end"]
    # the CPU writes no device plane: trace-derived metrics are left out
    on_cpu = {"slot_occupancy.serve", "decode_step_ms.serve",
              "experts_touched.serve", "prefill_ms.serve",
              "host_gap_ms.serve", "local_assignments.serve"}
    want = [n for n in want if not trace or n in on_cpu]
    assert set(want) <= set(out["metrics"]), out["metrics"]
    for name, m in out["metrics"].items():
        assert np.isfinite(m["value"]) and m["unit"], name
    if trace:
        assert 0 < out["metrics"]["experts_touched.serve"]["value"] <= 100
        assert 0 < out["metrics"]["local_assignments.serve"]["value"] <= 100
    json.dumps(out)
