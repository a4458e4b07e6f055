"""The latent-attention expert decoder's part of the benchmark: its
count module against hand-worked shapes, the job wrapper's work, the
scope-roofline reader, and a toy rehearsal of the cell's job through
``run.measure``. Run by hand with the benchmark's other tests:

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

from benchmarks import latent_moe_counts as counts, peaks  # noqa: E402
from benchmarks.jobs import serve_latent_moe as job  # noqa: E402
from benchmarks.readers import engine_report, scope_ops  # noqa: E402

CELL = "xing4-29b-serve-longctx-batch"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json("benchmarks", "configs", "xing4.0-29b-a4b-d6.json")


# -------------------------------------------------------------- counts
def test_parameters_against_hand_counts(cfg):
    """Worked by hand from the published widths (hidden 3584, 32 heads,
    q/kv ranks 768/512, head dims 128 + 64 / 128, experts 3 x 3584 x
    1024, hc_mult 4)."""
    attn = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
            + 4096 * 3584 + 768 + 512)
    assert counts.attention_params(cfg) == attn == 28_411_136
    assert counts.hc_params(cfg) == 2 * (14336 * 24 + 24 + 3) == 688_182
    assert counts.expert_params(cfg) == 11_010_048
    outside = attn + 688_182 + 2 * 3584 + 3584 * 64 + 64 + 11_010_048
    assert counts.layer_params(cfg, False, routed=False) == outside \
        == 40_345_974
    assert counts.layer_params(cfg, False) == outside + 64 * 11_010_048 \
        == 744_989_046
    assert counts.layer_params(cfg, True) == 128_196_918
    # what the program allocates (LazyGuard, PR 28): 4.792669828 B
    assert counts.model_params(cfg) == 4_792_669_828
    whole = dict(cfg, num_hidden_layers=40, first_k_dense_replace=2)
    assert round(counts.model_params(whole) / 1e9, 1) == 29.5


def test_decode_bytes_from_hand_worked_shapes(cfg):
    fixed = (128_196_918 + 5 * 40_345_974 + 3584 * 131072 + 3584) * 2
    assert counts.decode_bytes_per_step(cfg, 0, 0) == fixed == 1_599_384_840
    # the edges of experts_touched: no expert, and all 5 x 64
    assert counts.decode_bytes_per_step(cfg, 320, 0) - fixed == \
        320 * 3 * 3584 * 1024 * 2 == 7_046_430_720
    # one resident token: 512 + 64 numbers, bf16, six layers
    assert counts.decode_bytes_per_step(cfg, 0, 1) - fixed == 6912
    assert counts.latent_bytes_per_token(cfg) == 6 * 1152
    # the issue's step: 87 % of the experts, 32 x 4.3 k tokens
    step = counts.decode_bytes_per_step(cfg, 0.87 * 320, 32 * 4300)
    assert 8.5e9 < step < 8.9e9
    # rows x 2 x weights passed, plus 4 x heads x 576 a token a layer
    f0 = counts.decode_flops_per_step(cfg, 32, 0)
    assert counts.decode_flops_per_step(cfg, 32, 1000) - f0 == \
        4 * 1000 * 6 * 32 * 576
    per_row = (6 * (28_411_136 + 2 * 14336 * 24) + 3 * 3584 * 9216
               + 5 * (3584 * 64 + 5 * 11_010_048) + 3584 * 131072)
    assert f0 == 2 * 32 * per_row


def test_job_work_and_readers(cfg):
    rep = lambda n, touched, resident: {
        "experts_touched": {"count": n, "sum": touched},
        "resident_tokens": {"count": n, "sum": resident}}
    pair = (rep(10, 2000.0, 1e6), rep(30, 2000.0 + 20 * 280, 1e6 + 20 * 137600))
    assert job.window_mean(pair, "experts_touched") == pytest.approx(280.0)
    assert job.window_mean(pair, "no_such_histogram") is None
    assert job.window_mean((rep(5, 1, 1), rep(5, 1, 1)), "experts_touched") \
        is None
    work = job.step_work(cfg, 32, 280.0, 137600.0)
    assert work["moe_experts_bytes_per_step"] == 280 * 22_020_096
    assert work["latent_moe_decode_bytes_per_step"] == \
        1_599_384_840 + 280 * 22_020_096 + 137600 * 6912
    # the parent has neither histogram: no work, no metric, no raise
    assert job.step_work(cfg, 32, None, 137600.0) == {}
    obs = {"engine_report": pair, "engine": {"routed_expert_slots": 320}}
    spec = _json("benchmarks", "layer_metrics", "experts_touched.serve.json")
    assert engine_report.read(spec["source"], obs) == \
        pytest.approx(100 * 280 / 320)
    assert engine_report.read(spec["source"],
                              {"engine_report": ({}, {}), "engine": {}}) is None


def test_decisive_rows_and_the_judged_line(capsys):
    """The positions the served path is compared at are chosen from the
    reference's margins alone; the verdict holds four readings to three
    limits and prints each beside its limit."""
    import types

    from benchmarks.reference import latent_moe_decoder as ref

    routing = [(None, np.array([.9, .1, .5, .7, .3, .8])),
               (None, np.array([.2, .9, .6, .4, .9, .7]))]
    least = ref.least_margins(routing)
    np.testing.assert_allclose(least, [.2, .1, .5, .4, .3, .7])
    assert ref.decisive_rows(least, 1, 6, 3).tolist() == [2, 3, 5]
    assert ref.decisive_rows(least, 0, 3, 5).tolist() == [0, 1, 2]
    np.testing.assert_allclose(
        ref.relative_errors([[3.0, 4.0]], [[3.0, 0.0]]), [4 / 3])
    ctx = types.SimpleNamespace(reference=ref)
    side = {"least_margin": 0.01, "least_margin_median": 0.003}
    sound = {"decode_err": np.full(256, ref.PATH_ERR / 2),
             "prefill_err": np.full(8, ref.PATH_ERR / 2),
             "ffn_err": np.full(5120, ref.FFN_ERR / 2),
             "route_elsewhere": 0.0, "route_decided": 5120}
    assert job.judge(ctx, side, sound)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["line"] == "check_path" and line["ok"] \
        and line["allowed_path_err_p90"] == ref.PATH_ERR \
        and line["allowed_ffn_err_p90"] == ref.FFN_ERR
    # a ninth of the positions spoiled passes no longer at an eighth
    spoiled = dict(sound, decode_err=np.where(
        np.arange(256) % 8 == 0, 1.0, ref.PATH_ERR / 2))
    assert not job.judge(ctx, side, spoiled)
    for key, bad in (("prefill_err", np.full(8, 2 * ref.PATH_ERR)),
                     ("ffn_err", np.full(5120, 2 * ref.FFN_ERR)),
                     ("route_elsewhere", 2 * ref.ROUTE_ELSEWHERE),
                     ("decode_err", np.full(256, np.nan))):
        assert not job.judge(ctx, side, dict(sound, **{key: bad})), key


def test_scope_ops_counts_the_unscoped_grouped_matmuls():
    """Two runs of the decode program: 1 ms under ``moe_experts``, 4.5 ms
    of ``ragged-dot-none`` with no scope (overlapping 1 ms of another
    one), 2 ms of something else. The ms reading takes the first two,
    the roofline share divides the job's bytes by them."""
    ms = lambda a, b, path: (int(a * 1e6), int(b * 1e6), path)
    run = [ms(0, 1, "jit(_decode_body)/model/1/mlp/moe_experts/sort"),
           ms(1, 4, "ragged-dot-none"), ms(3, 5, "ragged-dot-none"),
           ms(5, 5.5, "ragged-dot-metadata"),
           ms(6, 8, "jit(_decode_body)/lm_head/dot_general")]
    shifted = [(a + 10**7, b + 10**7, p) for a, b, p in run]
    obs = {"trace": {"busy_s": 1.0}, "peaks": peaks.PEAKS["TPU v5 lite"],
           "work": {"moe_experts_bytes_per_step": 819e9 * 2.75e-3},
           "program_trace": {"chips": [{"by_span": {}, "idle_s": 0.0,
                                        "programs": {"jit__decode_body": {
                                            "runs": 2, "run_s": 0.016,
                                            "ops": run + shifted}}}]}}
    spec = _json("benchmarks", "layer_metrics", "moe_experts_ms.serve.json")
    assert scope_ops.read(spec["source"], obs) == pytest.approx(5.5)
    roof = _json("benchmarks", "layer_metrics",
                 "moe_experts_roofline.serve.json")["source"]
    assert scope_ops.read(roof, obs) == pytest.approx(50.0)
    assert scope_ops.read(roof, dict(obs, work={})) is None
    # the parent: no trace at all, or a program without these operations
    assert scope_ops.read(roof, {}) is None
    bare = dict(obs, program_trace={"chips": [{
        "by_span": {}, "idle_s": 0.0, "programs": {"jit__decode_body": {
            "runs": 1, "run_s": 0.002, "ops": run[-1:]}}}]})
    assert scope_ops.read(spec["source"], bare) is None


def test_cell_files_say_what_the_issue_asks(cfg):
    cell = _json("benchmarks", "workloads", f"{CELL}.json")
    mix = _json("benchmarks", "traffic", f"{cell['traffic']}.json")
    assert cell["engine"] == {"max_batch_size": 32, "max_seq_len": 8192,
                              "page_size": 16, "min_bucket": 128,
                              "cache_dtype": "bfloat16"}
    assert (mix["clients"], mix["requests_per_client"]) == (32, 8)
    assert (mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == (3072, 4096)
    assert max(cell["check"]["prompt_lens"]) + cell["check"]["max_new"] \
        <= cell["check"]["pad_to"]
    row = cfg["published"]
    assert row == {"num_hidden_layers": 40, "first_k_dense_replace": 2,
                   "max_position_embeddings": 262144,
                   "num_nextn_predict_layers": 1}
    assert (cfg["n_routed_experts"], cfg["vocab_size"], cfg["hc_mult"],
            cfg["hc_sinkhorn_iters"]) == (64, 131072, 4, 20)


# ----------------------------------------------------------- rehearsal
TOY = {"builder": "latent_moe_decoder", "vocab_size": 512, "hidden_size": 64,
       "intermediate_size": 128, "moe_intermediate_size": 32,
       "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
       "n_shared_experts": 1, "num_experts_per_tok": 2,
       "routed_scaling_factor": 2.0, "norm_topk_prob": True, "hc_mult": 4,
       "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
       "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 0,
       "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
       "rope_theta": 10000, "tie_word_embeddings": False,
       "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
       "topk_group": 1, "moe_layer_freq": 1,
       "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 32}}
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
        check={"prompt_lens": [24, 27, 30, 32], "max_new": 4, "pad_to": 48},
        path_check={"tokens": 32, "from": 4, "steps": 2, "prefill_rows": 2,
                    "ffn_rows": 16})
    files = (TOY_MANIFEST, {"chips": 1}, spec, TOY, TOY_MIX)
    out = run.measure("toy-latent-moe", 2**31 + 5, 2.0, trace, files,
                      jax.devices()[:1])
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    want = cell["per_layer"] if trace else cell["end_to_end"]
    # the CPU writes no device plane: trace-derived metrics are left out
    on_cpu = {"slot_occupancy.serve", "decode_step_ms.serve",
              "experts_touched.serve", "prefill_ms.serve",
              "host_gap_ms.serve"}
    want = [n for n in want if not trace or n in on_cpu]
    assert set(want) <= set(out["metrics"]), out["metrics"]
    for name, m in out["metrics"].items():
        assert np.isfinite(m["value"]) and m["unit"], name
    if trace:
        assert 0 < out["metrics"]["experts_touched.serve"]["value"] <= 100
    json.dumps(out)
