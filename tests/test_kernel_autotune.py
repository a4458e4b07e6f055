"""Kernel block-size autotuner (kernels/autotune.py) and the tuner
that drives it (tools/kernel_tune.py).

Everything here runs on CPU: the measured search is driven by an
injectable fake clock (zero wall-time dependence), and the tuner's one
Pallas subject, flash attention, whose stock kernel has no interpret
path, is shown a faked chip and given runnables that do nothing.
"""
import json

import numpy as np
import pytest

import paddle_tpu.kernels.autotune as at
from paddle_tpu import kernels
from paddle_tpu.kernels import flash_attention as fa


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the process-wide tune cache at a throwaway file."""
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv(at.ENV_CACHE, path)
    at.reset_cache()
    yield path
    at.reset_cache()


# ------------------------------------------------------------- fake clock


class _FakeClock:
    """Deterministic time source: candidates advance it by their
    scripted cost when they 'run'."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_fake_timer_search_picks_fastest():
    clock = _FakeClock()
    costs = {8: 5.0, 16: 1.0, 32: 3.0}
    built = []

    def build(cfg):
        c = costs[cfg["block"]]
        built.append(cfg["block"])

        def fn():
            clock.t += c
            return None

        return fn

    best, table = at.measured_search(
        [{"block": b} for b in (8, 16, 32)], build,
        iters=2, windows=3, clock=clock, sync=lambda x: None,
    )
    assert best == {"block": 16}
    assert built == [8, 16, 32]  # one build (compile) per candidate
    # per-call seconds = cost: 2 iters * 1.0 / 2
    assert table[0]["median_s"] == pytest.approx(1.0)
    assert [r["config"]["block"] for r in table] == [16, 32, 8]
    assert all(len(r["window_s"]) == 3 for r in table)


FLASH_SPEC = {"b": 4, "s": 2048, "h": 16, "d": 128, "causal": True}
FLASH_SIG = at.flash_sig(4, 2048, 2048, 16, 128, True)
FLASH_Q = np.broadcast_to(np.float32(0), (4, 2048, 16, 128))  # no bytes


@pytest.fixture
def fake_flash_builds(force_tpu, monkeypatch):
    """``tools.kernel_tune`` with flash as its subject and no chip: the
    faked TPU of ``force_tpu`` lets ``tune_shape`` through, and every
    candidate's runnable does nothing (the clock is injected). Yields
    the list of kernels a build factory was asked for."""
    import tools.kernel_tune as kt

    builds = []

    def factory(kernel, spec):
        builds.append(kernel)
        return lambda config: (lambda: None)

    monkeypatch.setattr(kt, "_build_factory", factory)
    return builds


def test_tune_shape_cache_hit_runs_zero_measurements(tmp_cache,
                                                     fake_flash_builds):
    """The cache-or-measure driver (tools.kernel_tune.tune_shape)
    short-circuits on a hit BEFORE building or running anything."""
    import tools.kernel_tune as kt

    cache = at.TuneCache(tmp_cache)
    row = kt.tune_shape("flash_attention", FLASH_SPEC, cache, iters=1,
                        windows=1, clock=_FakeClock(),
                        sync=lambda x: None)
    assert row["measured"] > 0 and not row["cache_hit"]
    assert fake_flash_builds == ["flash_attention"]
    # second tune: cache hit, the build/run machinery is never touched
    row2 = kt.tune_shape("flash_attention", FLASH_SPEC, cache, iters=1,
                         windows=1)
    assert row2["cache_hit"] and row2["measured"] == 0
    assert row2["config"] == row["config"]
    assert fake_flash_builds == ["flash_attention"]


def test_cache_file_roundtrip(tmp_cache):
    cache = at.TuneCache(tmp_cache)
    cache.record("k", "sigA", {"block_q": 128}, device="devX",
                 timings_ms={"a": 1.0})
    fresh = at.TuneCache(tmp_cache)
    assert fresh.lookup("k", "sigA", device="devX",
                        count=False) == {"block_q": 128}
    assert fresh.lookup("k", "sigB", device="devX", count=False) is None
    entry = fresh.entry("k", "sigA", device="devX")
    assert entry["source"] == "measured" and entry["timings_ms"]
    data = json.load(open(tmp_cache))
    assert data["version"] == at.CACHE_VERSION


def test_corrupt_cache_degrades_to_seeded_defaults(tmp_cache):
    with open(tmp_cache, "w") as f:
        f.write('{"entries": {"truncated')
    before = at.cache_counter().series().get((("event", "corrupt"),), 0)
    cache = at.get_cache()
    assert cache.lookup("flash_attention", "whatever") is None
    assert cache.corrupt
    after = at.cache_counter().series().get((("event", "corrupt"),), 0)
    assert after == before + 1
    # flash selection falls back to the seeded v5e triple
    bs = fa._tuned_block_sizes(4096, 4096, b=4, h=16, d=128)
    assert (bs.block_q, bs.block_k_major, bs.block_k) == (512, 1024, 512)


def test_stale_cache_entry_is_signalled_fallback(tmp_cache):
    at.get_cache().record(
        "flash_attention", at.flash_sig(2, 256, 256, 2, 64, True),
        # 96 does not divide S=256: stale/illegal
        {"block_q": 96, "block_k_major": 256, "block_k": 256},
    )
    at.reset_warned()
    before = at.fallback_counter().value
    with pytest.warns(RuntimeWarning, match="stale-config"):
        assert fa._resolve_config(256, 256, b=2, h=2, d=64)[1] == "seed"
    assert at.fallback_counter().value == before + 1
    # one-shot: a second lookup counts but does not warn again
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert fa._resolve_config(256, 256, b=2, h=2, d=64)[1] == "seed"
    assert at.fallback_counter().value == before + 2


def test_checked_in_cache_parses_and_entries_are_legal():
    cache = at.TuneCache(at.DEFAULT_CACHE_PATH)
    keys = cache.keys()
    assert keys, "checked-in tune cache is empty"
    assert not cache.corrupt
    for key in keys:
        kernel, sig, device = key.split("|")
        entry = cache._load()[key]
        cfg = entry["config"]
        if kernel == "flash_attention":
            sq = int(sig.split("_sq")[1].split("_")[0])
            sk = int(sig.split("_sk")[1].split("_")[0])
            assert at.flash_config_legal(sq, sk, cfg), key


# ------------------------------------------------------ candidate configs


def test_flash_candidates_divisibility():
    for cfg in at.flash_block_candidates(2176, 2176):
        assert at.flash_config_legal(2176, 2176, cfg)
    assert at.flash_block_candidates(2050, 2050) == []
    # seed-shaped candidates present for seed-friendly shapes
    cands = at.flash_block_candidates(4096, 4096)
    assert {"block_q": 512, "block_k_major": 1024, "block_k": 512} in cands


def test_fallback_signal_for_indivisible_shape(force_tpu):
    at.reset_warned()
    q = np.zeros((4, 2050, 16, 128), np.float32)
    before = at.fallback_counter().series().get(
        (("kernel", "flash_attention"), ("reason", "indivisible")), 0)
    with pytest.warns(RuntimeWarning, match="indivisible"):
        ok, cfg, reason = fa._select(q, q, q, True)
    assert not ok and reason == "fallback:indivisible"
    after = at.fallback_counter().series().get(
        (("kernel", "flash_attention"), ("reason", "indivisible")), 0)
    assert after == before + 1
    # the paddle_kernels_* series are visible in the Prometheus text
    from paddle_tpu.observability import get_registry

    text = get_registry().prometheus_text()
    assert "paddle_kernels_fallback_total" in text
    assert 'reason="indivisible"' in text


def test_score_bytes_threshold_single_home(force_tpu):
    assert kernels.SCORE_BYTES_THRESHOLD == 2 << 30
    assert kernels.SCORE_BYTES_THRESHOLD is fa.SCORE_BYTES_THRESHOLD
    # non-causal selection flips exactly at the threshold:
    # score_bytes = 4*B*H*S^2; S=4096, H=8, B=4 -> exactly 2 GiB (not >)
    q = np.zeros((4, 4096, 8, 128), np.float32)
    assert 4 * 4 * 8 * 4096 * 4096 == kernels.SCORE_BYTES_THRESHOLD
    assert not fa._pallas_ok(q, q, q, causal=False)
    q9 = np.zeros((4, 4096, 9, 128), np.float32)  # one head past it
    assert fa._pallas_ok(q9, q9, q9, causal=False)


# ------------------------------------------------------ measured verdicts


def test_measured_composed_win_is_not_installed(tmp_cache, force_tpu):
    """Review pin: the tuner must never install a measured performance
    regression. An entry whose fused_beats_composed verdict is False
    stays a cache hit (no re-measurement) but selection keeps the
    composed path; an entry WITHOUT the verdict (seeded, hand-written)
    still activates."""
    cfg = {"block_q": 256, "block_k_major": 512, "block_k": 256}
    at.get_cache().record("flash_attention", FLASH_SIG, cfg,
                          extra={"fused_beats_composed": False},
                          save=False)
    ok, _, reason = fa._select(FLASH_Q, FLASH_Q, FLASH_Q, True)
    assert not ok and reason == "policy:measured-composed-wins"
    assert at.lookup("flash_attention", FLASH_SIG) == cfg  # still a hit

    at.get_cache().record("flash_attention", FLASH_SIG, cfg, save=False)
    assert fa._select(FLASH_Q, FLASH_Q, FLASH_Q, True) == (
        True, cfg, "pallas:cached")


def test_flash_cached_composed_verdict_two_regimes(tmp_cache, force_tpu):
    """A cached flash entry measured composed-faster keeps composed in
    the time regime; in the memory regime (composed would materialize
    >2 GiB of scores) pallas with the cached config still runs."""
    at.get_cache().record(
        "flash_attention", at.flash_sig(4, 2048, 2048, 16, 128, True),
        {"block_q": 512, "block_k_major": 1024, "block_k": 512},
        extra={"fused_beats_composed": False}, save=False,
    )
    q = np.zeros((4, 2048, 16, 128), np.float32)
    ok, cfg, reason = fa._select(q, q, q, True)
    assert not ok and reason == "policy:measured-composed-wins"

    at.get_cache().record(
        "flash_attention", at.flash_sig(8, 8192, 8192, 16, 128, True),
        {"block_q": 512, "block_k_major": 1024, "block_k": 512},
        extra={"fused_beats_composed": False}, save=False,
    )
    q2 = np.zeros((8, 8192, 16, 128), np.float32)
    ok2, cfg2, reason2 = fa._select(q2, q2, q2, True)
    assert ok2 and reason2 == "pallas:cached"
    assert cfg2 == {"block_q": 512, "block_k_major": 1024,
                    "block_k": 512}


def test_tune_shape_records_verdict(tmp_cache, fake_flash_builds):
    """A constant injected clock makes every candidate tie, so fused
    does NOT beat composed: the recorded entry carries the verdict and
    selection refuses to activate the fused path."""
    import tools.kernel_tune as kt

    cache = at.TuneCache(tmp_cache)
    row = kt.tune_shape(
        "flash_attention", FLASH_SPEC, cache, iters=1, windows=1,
        clock=lambda: 0.0, sync=lambda x: None,
    )
    assert row["fused_beats_composed"] is False
    assert cache.entry("flash_attention",
                       FLASH_SIG)["fused_beats_composed"] is False
    # the process-wide cache reads the same file the driver wrote
    assert fa._select(FLASH_Q, FLASH_Q, FLASH_Q, True)[2] == \
        "policy:measured-composed-wins"


def test_measured_search_skips_failing_candidate():
    """Review pin: one candidate whose build/warmup raises (on-chip: a
    Mosaic rejection / VMEM overflow) is skipped and counted — it must
    not abort the search for the rest."""
    clock = _FakeClock()

    def build(cfg):
        if cfg["block"] == 16:
            raise RuntimeError("mosaic says no")

        def fn():
            clock.t += float(cfg["block"])
            return None

        return fn

    before = at.tune_error_counter().value
    with pytest.warns(RuntimeWarning, match="mosaic says no"):
        best, table = at.measured_search(
            [{"block": b} for b in (8, 16, 32)], build,
            iters=1, windows=1, clock=clock, sync=lambda x: None,
        )
    assert best == {"block": 8}
    assert [r["config"]["block"] for r in table] == [8, 32]
    assert at.tune_error_counter().value == before + 1


def test_flash_selection_path_label_carries_reason(force_tpu):
    """Review pin: composed picks publish WHY as the path label — the
    cross-length causal decode shape (paying the full O(S^2) bill) is
    its own series, not an anonymous "composed"."""
    q = np.zeros((1, 128, 2, 64), np.float32)
    k = np.zeros((1, 4096, 2, 64), np.float32)
    key = (("kernel", "flash_attention"),
           ("path", "policy:cross-length-causal"))
    before = at.selection_counter().series().get(key, 0)
    fa.flash_attention_fwd(q, k, k, causal=True)
    assert at.selection_counter().series().get(key, 0) == before + 1


def test_run_tune_second_run_is_all_hits(tmp_cache, fake_flash_builds):
    from tools.kernel_tune import run_tune

    specs = [("flash_attention", FLASH_SPEC)]
    rec = run_tune(cache_path=tmp_cache, specs=specs, iters=1, windows=1,
                   clock=_FakeClock(), sync=lambda x: None)
    assert rec["shapes_measured"] == 1 and rec["cache_hits"] == 0
    rec2 = run_tune(cache_path=tmp_cache, specs=specs, iters=1,
                    windows=1)
    assert rec2["shapes_measured"] == 0 and rec2["cache_hits"] == 1
    assert rec2["cache_hit_rate"] == 1.0


def test_entries_of_deleted_kernels_load_and_change_no_program(tmp_cache):
    """A tune cache written while the four opt-in kernels existed still
    holds their entries, at the very shapes that used to switch a toy
    Llama onto them. They are keyed strings nothing looks up: the file
    loads, its entries are counted, and the forward and the paged
    decode step lower to the text they lower to without it."""
    import re

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generation

    paddle.seed(0)
    cfg = LlamaConfig.tiny()  # hidden 64, 4 heads of 16, vocab 1000
    net = LlamaForCausalLM(cfg)
    net.eval()
    arena = generation.alloc_kv_caches(cfg, 9, 8, "bfloat16")
    tbl = jnp.asarray(1 + np.arange(8).reshape(2, 4), jnp.int32)

    def lowered():
        def forward(ids):
            with tape.trace_scope(), tape.no_grad():
                return net(Tensor(ids)).value

        fwd = jax.jit(forward)
        dec = jax.jit(lambda tok, caches, pos: generation.decode_step(
            net, tok, caches, pos, page_table=tbl))
        texts = (fwd.lower(jnp.zeros((2, 16), jnp.int32)).as_text(),
                 dec.lower(jnp.zeros((2, 1), jnp.int32), arena,
                           jnp.zeros((2,), jnp.int32)).as_text())
        return [re.sub(r"loc\(.*\)", "", t) for t in texts]

    before = lowered()
    blocks = {"block_rows": 8, "block_cols": 125}
    entries = {
        "rope_attention|b2_s16_h4_d16|cpu": {"config": {"block_q": 8}},
        "rms_norm_matmul|r32_h64_n1000|cpu": {"config": blocks},
        "paged_attention|b2_p4_ps8_h4_kv4_d16|cpu":
            {"config": {"block_kvh": 4}},
        "int8_matmul|r2_h64_n1000|cpu": {"config": blocks},
    }
    with open(tmp_cache, "w") as f:
        json.dump({"version": at.CACHE_VERSION, "entries": entries}, f)
    at.reset_cache()
    cache = at.get_cache()
    assert cache.keys() == sorted(entries) and not cache.corrupt
    assert lowered() == before
