"""The KDA / NoPE-MLA expert decoder's part of the benchmark: its count
module against the program's own parameter shapes and hand-worked
sizes, the job wrapper's work and verdict, the three new metric files
through their readers, and a toy rehearsal of the cell's job through
``run.measure``. Run by hand with the benchmark's other tests:

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

from benchmarks import kda_mla_moe_counts as counts, peaks  # noqa: E402
from benchmarks.jobs import serve_kda_mla_moe as job  # noqa: E402
from benchmarks.readers import (  # noqa: E402
    device_trace,
    engine_report,
    scope_ops,
)

CELL = "kimi-linear-serve-longdoc-batch32"
CONFIG = "kimi-linear-48b-a3b-ep2-d5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    from benchmarks.models import kda_mla_moe_decoder as builder

    shapes = builder.parameter_shapes(cfg)
    size = lambda pre: sum(int(np.prod(s)) for k, s in shapes.items()
                           if k.startswith(pre))
    assert size("") == counts.model_params(cfg) == 4_282_951_552
    assert size("model.layers.3.mixer.") == counts.mla_params(cfg) \
        == 29_114_880
    for i in (0, 1, 2, 4):
        assert size(f"model.layers.{i}.mixer.") == counts.kda_params(cfg) \
            == 39_518_368
    assert shapes["model.layers.3.mixer.q_proj.weight"] == (2304, 32 * 192)
    assert shapes["model.layers.0.mlp.gate_up_proj.weight"] == (2304, 18432)
    assert shapes["model.layers.2.mlp.gate_weight"] == (2304, 256)
    assert shapes["model.layers.2.mlp.experts_gate_up"] == (128, 2304, 2048)
    assert counts.expert_params(cfg) == 3 * 2304 * 1024
    for i in range(5):
        assert size(f"model.layers.{i}.") == counts.layer_params(cfg, i)
    assert size("model.layers.0.") == 103_223_968
    assert size("model.layers.1.") == 953_160_352
    assert size("model.layers.3.") == 942_756_864
    assert shapes["lm_head.weight"] == (2304, 81920)
    assert (counts.mla_layers(cfg), counts.kda_layers(cfg),
            counts.expert_layers(cfg)) == (1, 4, 4)
    # 8.57 GB in bf16, as the configuration's file says
    assert round(2 * counts.model_params(cfg) / 1e9, 2) == 8.57
    assert "4 282 951 552" in cfg["stands_for"]


def test_decode_bytes_from_hand_worked_shapes(cfg):
    outside = 29_114_880 + 4 * 39_518_368 + 5 * 2 * 2304 \
        + 3 * 2304 * 9216 + 4 * (2304 * 256 + 7_077_888) \
        + 2304 * 81920 + 2304
    state = 4 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert counts.row_state_bytes(cfg) == state == 8_683_520
    assert counts.latent_bytes_per_token(cfg) == 1280
    assert counts.kda_step_bytes(cfg, 32) == 2 * 32 * state
    fixed = 2 * outside + 2 * 32 * state
    assert counts.decode_bytes_per_step(cfg, 0, 0, 32) == fixed
    # the edges of experts_touched: none, and all 4 x 128 held
    assert counts.decode_bytes_per_step(cfg, 512, 0, 32) - fixed == \
        512 * 3 * 2304 * 1024 * 2
    assert counts.decode_bytes_per_step(cfg, 0, 1, 32) - fixed == 1280
    # the issue's step: 63 % of the held experts, 32 x 15 k tokens
    step = counts.decode_bytes_per_step(cfg, 0.63 * 512, 32 * 15000, 32)
    assert 6.6e9 < step < 6.8e9
    # one 16384 bucket: 256 chunks x 32 heads x 4 layers
    per_chunk = 4 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert counts.kda_chunk_flops(cfg, 16384) == 4 * 32 * 256 * per_chunk
    f0 = counts.decode_flops_per_step(cfg, 32, 0)
    assert counts.decode_flops_per_step(cfg, 32, 1000) - f0 == \
        4 * 1000 * 32 * 576


def test_job_work_and_readers(cfg):
    rep = lambda n, touched, resident, local, rows: {
        "experts_touched": {"count": n, "sum": touched},
        "resident_tokens": {"count": n, "sum": resident},
        "local_assignments": {"count": n, "sum": local},
        "dispatch_rows": {"count": n, "sum": rows}}
    pair = (rep(10, 1000.0, 1e6, 2560.0, 5120.0),
            rep(30, 1000.0 + 20 * 320, 1e6 + 20 * 480000,
                2560.0 + 20 * 512, 5120.0 + 20 * 768))
    engine = {"max_batch_size": 32}
    work = job.step_work(cfg, engine, 16384, 320.0, 480000.0)
    assert work["moe_experts_bytes_per_step"] == 320 * 14_155_776
    assert work["latent_read_bytes_per_step"] == 480000 * 1280
    assert work["kda_mla_moe_decode_bytes_per_step"] == \
        counts.decode_bytes_per_step(cfg, 320.0, 480000.0, 32)
    assert work["kda_step_bytes_per_step"] == 2 * 32 * 8_683_520
    # a program without the histograms: the shape-only work alone
    assert set(job.step_work(cfg, engine, 16384, None, 480000.0)) == {
        "kda_step_bytes_per_step", "kda_chunk_flops_per_prefill"}
    obs = {"engine_report": pair,
           "engine": {"routed_expert_slots": 512, "assignment_slots": 1024}}
    spec = lambda n: _json("benchmarks", "layer_metrics", f"{n}.json")
    read = lambda n, o: engine_report.read(spec(n)["source"], o)
    assert read("dispatch_rows.serve", obs) == pytest.approx(75.0)
    assert read("local_assignments.serve", obs) == pytest.approx(50.0)
    assert read("experts_touched.serve", obs) == pytest.approx(62.5)
    # the parent: no such histogram; nothing raises
    assert read("dispatch_rows.serve",
                {"engine_report": ({}, {}), "engine": {}}) is None
    lengths = job.linear.path_lengths(16384, 32, 32, 128)
    assert lengths[0] + 32 == 16384 and lengths.min() == 12384


def test_the_judged_line_holds_thirteen_readings(capsys):
    from benchmarks.reference import kda_mla_moe_decoder as ref

    ctx = types.SimpleNamespace(reference=ref)
    sound = {"decode_err": np.full(1024, ref.PATH_ERR / 2),
             "prefill_err": np.full(32, ref.PATH_ERR / 2),
             "state_err": np.full(128, ref.PATH_STATE_ERR / 2),
             "stepped_state_err": np.full(128, ref.PATH_STATE_ERR / 2),
             "kernel_state_err": np.full(32, ref.KERNEL_STATE_ERR / 2),
             "mla_step_err": np.full(32, ref.MLA_STEP_ERR / 2),
             "ffn_err": np.full(4096, ref.FFN_ERR / 2),
             "route_elsewhere": 0.0, "route_decided": 4096,
             "adopted_latent_err": np.full(16384, ref.ADOPTED_ERR / 2),
             "adopted_state_err": np.full(128, ref.PATH_STATE_ERR / 2),
             "adopted_tail_err": np.full(12, ref.ADOPTED_ERR / 2)}
    assert job.judge(ctx, sound)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["line"] == "check_path" and line["ok"] \
        and line["allowed_mla_step_err_p90"] == ref.MLA_STEP_ERR
    for key, bad in (("decode_err", np.full(1024, 2 * ref.PATH_ERR)),
                     ("decode_err", np.where(np.arange(1024) % 8 == 0,
                                             2 * ref.PATH_ERR_P90, 0.0)),
                     ("kernel_state_err", np.full(32, 1e-3)),
                     ("prefill_err", np.full(32, 2 * ref.PATH_ERR)),
                     ("state_err", np.where(
                         np.arange(128) % 3, 2 * ref.PATH_STATE_ERR, 0.0)),
                     ("stepped_state_err",
                      np.full(128, 2 * ref.PATH_STATE_ERR)),
                     ("mla_step_err", np.where(
                         np.arange(32) % 4 == 0, 2 * ref.MLA_STEP_ERR, 0.0)),
                     ("ffn_err", np.full(4096, 2 * ref.FFN_ERR)),
                     ("route_elsewhere", 2 * ref.ROUTE_ELSEWHERE),
                     ("adopted_latent_err", np.full(16384, 1.0)),
                     # every eighth page lost
                     ("adopted_latent_err", np.where(
                         np.arange(16384) // 16 % 8 == 0, 1.0, 0.0)),
                     ("adopted_state_err", np.full(128, 1.0)),
                     ("adopted_tail_err", np.full(12, 1.4)),
                     ("decode_err", np.full(1024, np.nan))):
        assert not job.judge(ctx, dict(sound, **{key: bad})), key


def test_new_metric_files_read_a_recorded_trace():
    """Two runs of the decode program (2 ms under ``attn_core`` in the
    MLA layer, inside the span ladder's conditional; 2 ms elsewhere):
    each new trace metric through its own reader."""
    ms = lambda a, b, path: (int(a * 1e6), int(b * 1e6), path)
    core = "jit(_decode_body)/model/3/mixer/attn_core/"
    run = [ms(0, 0.5, core + "dynamic_update_slice"),
           ms(0.5, 2, core + "cond/branch_7_fun/dot_general"),
           ms(2, 3, "jit(_decode_body)/model/1/mixer/kda_step/mul"),
           ms(3, 4, "jit(_decode_body)/lm_head/dot_general")]
    shifted = [(a + 10**7, b + 10**7, p) for a, b, p in run]
    obs = {"trace": {"busy_s": 1.0, "window_s": 2.0, "chips": 1, "modules": {
               "jit__decode_body": [2.0, 0.008]}},
           "peaks": peaks.PEAKS["TPU v5 lite"],
           "work": {"latent_read_bytes_per_step": 819e9 * 1e-3,
                    "kda_mla_moe_decode_bytes_per_step": 819e9 * 2e-3},
           "program_trace": {"chips": [{"by_span": {}, "idle_s": 0.0,
                                        "programs": {
               "jit__decode_body": {"runs": 2, "run_s": 0.008,
                                    "ops": run + shifted}}}]}}
    spec = lambda n: _json("benchmarks", "layer_metrics", f"{n}.json")
    assert scope_ops.read(spec("latent_read_roofline.serve")["source"], obs) \
        == pytest.approx(50.0)
    assert device_trace.read(
        spec("decode_step_roofline.kda_mla_moe")["source"], obs) \
        == pytest.approx(50.0)
    # the parent: no such work; nothing raises
    bare = dict(obs, work={})
    for name in ("latent_read_roofline.serve",
                 "decode_step_roofline.kda_mla_moe"):
        s = spec(name)
        reader = {"scope_ops": scope_ops,
                  "device_trace": device_trace}[s["source"]["reader"]]
        assert reader.read(s["source"], bare) is None, name


def test_cell_files_say_what_the_issue_asks(cfg):
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    mix = _json("benchmarks", "traffic", f"{cell['traffic']}.json")
    assert cell["traffic"] == "closed-batch-32-p16k-o2k"
    assert cell["engine"] == {
        "max_batch_size": 32, "max_seq_len": 18432, "page_size": 16,
        "min_bucket": 128, "cache_dtype": "bfloat16", "max_queue_size": 64}
    assert (mix["kind"], mix["clients"], mix["requests_per_client"],
            mix["stagger_first"], mix["fill_timeout_s"]) == (
        "closed_loop", 32, 8, True, 300)
    assert (mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == (
        12288, 16384)
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (1024, 2048)
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] == \
        cell["engine"]["max_seq_len"]
    assert cell["check"]["prompt_lens"] == [12288, 16384]
    assert max(cell["check"]["prompt_lens"]) + cell["check"]["max_new"] \
        <= cell["check"]["pad_to"]
    assert cfg["published"] == {
        "num_hidden_layers": 27, "num_experts": 256,
        "vocab_size": 163840, "model_max_length": 1048576}
    assert set(cfg["reduced"]) == set(cfg["published"])
    # every key of the catalog row, no width changed
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        assert cfg["source"] == row["source_url"]
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
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_ms"):
            assert CELL in m["workloads"]


# ----------------------------------------------------------- rehearsal
TOY = {"builder": "kda_mla_moe_decoder", "vocab_size": 512,
       "hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 5,
       "first_k_dense_replace": 1, "num_attention_heads": 4,
       "num_key_value_heads": 4, "q_lora_rank": None, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "mla_use_nope": True, "rope_scaling": None,
       "linear_attn_config": {
           "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
           "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
       "num_experts": 8, "experts_first": 8,
       "published": {"num_experts": 16}, "num_shared_experts": 1,
       "num_experts_per_token": 4, "routed_scaling_factor": 2.446,
       "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
       "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
       "num_nextn_predict_layers": 0, "model_max_length": 128,
       "rms_norm_eps": 1e-5, "tie_word_embeddings": False}
TOY_MANIFEST = {"end_to_end": [
    {"name": "serve_tok_s", "unit": "tokens/s"},
    {"name": "itl_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]}
TOY_MIX = {"kind": "closed_loop", "clients": 4, "requests_per_client": 50,
           "prompt_len": {"dist": "uniform", "lo": 24, "hi": 32},
           "output_len": {"dist": "uniform", "lo": 8, "hi": 24}}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_keys(trace, monkeypatch):
    """The cell's job, builder, reference and metric files end to end
    on the CPU at a toy size. No number from here is a measurement."""
    import jax

    from benchmarks import run

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    spec = dict(cell, param_dtype="float32", trace_seconds=0.5, engine={
        "max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
        "min_bucket": 16, "cache_dtype": "float32"},
        check={"prompt_lens": [24, 32], "max_new": 4, "pad_to": 48},
        path_check={"tokens": 32, "steps": 3, "stride": 2, "ffn_rows": 16})
    files = (TOY_MANIFEST, {"chips": 1}, spec, TOY, TOY_MIX)
    out = run.measure("toy-kda-mla-moe", 2**31 + 5, 2.0, trace, files,
                      jax.devices()[:1])
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    want = cell["per_layer"] if trace else cell["end_to_end"]
    # the CPU writes no device plane: trace-derived metrics are left out
    on_cpu = {"slot_occupancy.serve", "decode_step_ms.serve",
              "experts_touched.serve", "prefill_ms.serve",
              "host_gap_ms.serve", "local_assignments.serve",
              "dispatch_rows.serve"}
    want = [n for n in want if not trace or n in on_cpu]
    assert set(want) <= set(out["metrics"]), out["metrics"]
    for name, m in out["metrics"].items():
        assert np.isfinite(m["value"]) and m["unit"], name
    if trace:
        for name in ("experts_touched.serve", "local_assignments.serve",
                     "dispatch_rows.serve"):
            assert 0 < out["metrics"][name]["value"] <= 100, name
    json.dumps(out)


def test_controls_tool_rehearses_every_planted_run(capsys):
    """``tools/kda_mla_moe_controls.py`` at a toy size on the CPU: every
    fault of the served tokens and every lower precision of the path
    check runs through the job's own functions and prints its line.
    No number from here is a reading."""
    from tools import kda_mla_moe_controls as tool

    assert tool.main(["--toy", "--seeds", "5"]) == 0
    lines = [json.loads(text) for text in capsys.readouterr().out.splitlines()
             if text.startswith("{")]
    served = {o["served"] for o in lines if "served" in o}
    assert served == {"as_served", *tool.SERVED_FAULTS}
    path = {o["path"]: o for o in lines if "path" in o}
    assert set(path) == {"as_stated", *tool.ADOPTION_FAULTS,
                         *tool.PATH_CONTROLS}
    for fault, must in tool.ADOPTION_FAULTS.items():
        assert must <= set(path[fault]["broke"]), fault
    assert path["as_stated"]["ok"] and not path["as_stated"]["broke"]
    # float32 at this size: the faults that are no rounding still show
    assert "stepped_state_err_median" in path["unfrozen_scan"]["broke"]
    assert path["fp8_experts"]["broke"] == ["ffn_err_p90"]
    assert "verdict" in lines[-1]
