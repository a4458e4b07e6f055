"""Kernel block-size autotuner (kernels/autotune.py) + fusion kernels.

Everything here runs on CPU: the measured search is driven by an
injectable fake clock (zero wall-time dependence), the fusion kernels
execute in pallas interpret mode, and parity is pinned BIT-EXACT under
jit (both paths compile in production — inside the train step / decode
step — so jitted parity is the contract that matters).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.kernels.autotune as at
from paddle_tpu import kernels
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import fused_norm_matmul as fnm
from paddle_tpu.kernels import fused_rope_attention as fra
from paddle_tpu.kernels.rope import build_rope_cache


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


def test_tune_shape_cache_hit_runs_zero_measurements(tmp_cache,
                                                     monkeypatch):
    """The cache-or-measure driver (tools.kernel_tune.tune_shape)
    short-circuits on a hit BEFORE building or running anything."""
    import tools.kernel_tune as kt

    builds = []
    real_factory = kt._build_factory

    def counting_factory(kernel, spec):
        builds.append(kernel)
        return real_factory(kernel, spec)

    monkeypatch.setattr(kt, "_build_factory", counting_factory)
    cache = at.TuneCache(tmp_cache)
    spec = {"rows": 8, "hidden": 32, "n_out": 128}
    row = kt.tune_shape("rms_norm_matmul", spec, cache, iters=1,
                        windows=1)
    assert row["measured"] > 0 and not row["cache_hit"]
    assert builds == ["rms_norm_matmul"]
    # second tune: cache hit, the build/run machinery is never touched
    row2 = kt.tune_shape("rms_norm_matmul", spec, cache, iters=1,
                         windows=1)
    assert row2["cache_hit"] and row2["measured"] == 0
    assert row2["config"] == row["config"]
    assert builds == ["rms_norm_matmul"]


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
        "rope_attention", at.rope_attention_sig(2, 64, 2, 16),
        {"block_q": 48},  # does not divide S=64: stale/illegal
    )
    at.reset_warned()
    before = at.fallback_counter().value
    with pytest.warns(RuntimeWarning, match="stale-config"):
        assert fra.rope_attention_select(2, 64, 2, 16) is None
    assert at.fallback_counter().value == before + 1
    # one-shot: a second select counts but does not warn again
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert fra.rope_attention_select(2, 64, 2, 16) is None
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


# ----------------------------------------------------------- parity pins


def _rand(shape, dtype, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_rope_attention_fwd_parity_bit_exact(dtype, causal):
    B, S, H, D = 2, 64, 4, 16
    q = _rand((B, S, H, D), dtype, 0)
    k = _rand((B, S, H, D), dtype, 1)
    v = _rand((B, S, H, D), dtype, 2)
    cos, sin = build_rope_cache(S, D)
    fused = jax.jit(lambda a, b, c: fra.rope_attention_fused(
        a, b, c, cos, sin, causal=causal, block_q=16))(q, k, v)
    ref = jax.jit(lambda a, b, c: fra.rope_attention_composed(
        a, b, c, cos, sin, causal=causal))(q, k, v)
    assert fused.dtype == q.dtype
    assert (np.asarray(fused) == np.asarray(ref)).all()


def test_rope_attention_bwd_parity():
    B, S, H, D = 2, 32, 2, 16
    q = _rand((B, S, H, D), jnp.float32, 0)
    k = _rand((B, S, H, D), jnp.float32, 1)
    v = _rand((B, S, H, D), jnp.float32, 2)
    cos, sin = build_rope_cache(S, D)

    def loss_fused(a, b, c):
        return fra.rope_attention_fused(a, b, c, cos, sin,
                                        block_q=8).sum()

    def loss_ref(a, b, c):
        return fra.rope_attention_composed(a, b, c, cos, sin).sum()

    gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_rope_attention_matches_unfused_path():
    """The fused kernel vs TODAY'S path (rope kernel then composed
    attention) — numerically equivalent within fp32 rounding."""
    from paddle_tpu.kernels.rope import rope_fused

    B, S, H, D = 2, 64, 4, 16
    q = _rand((B, S, H, D), jnp.float32, 0)
    k = _rand((B, S, H, D), jnp.float32, 1)
    v = _rand((B, S, H, D), jnp.float32, 2)
    cos, sin = build_rope_cache(S, D)
    fused = fra.rope_attention_fused(q, k, v, cos, sin, block_q=16)
    ref = fa._composed(rope_fused(q, cos, sin), rope_fused(k, cos, sin),
                       v, causal=True, scale=1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_norm_matmul_fwd_parity_bit_exact(dtype):
    x = _rand((16, 64), dtype, 0)
    w = _rand((64,), jnp.float32, 1)
    wm = _rand((64, 256), dtype, 2)
    fused = jax.jit(lambda a: fnm.rms_norm_matmul(
        a, w, wm, block_rows=8, block_cols=128))(x)
    ref = jax.jit(lambda a: fnm.rms_norm_matmul_composed(a, w, wm))(x)
    assert (np.asarray(fused) == np.asarray(ref)).all()


def test_norm_matmul_3d_and_bwd_parity():
    x = _rand((2, 8, 64), jnp.float32, 0)
    w = _rand((64,), jnp.float32, 1)
    wm = _rand((64, 128), jnp.float32, 2)
    fused = fnm.rms_norm_matmul(x, w, wm, block_rows=4, block_cols=64)
    assert fused.shape == (2, 8, 128)
    ref = fnm.rms_norm_matmul_composed(x, w, wm)
    assert (np.asarray(fused) == np.asarray(ref)).all()

    def lf(a, b, c):
        return fnm.rms_norm_matmul(a, b, c, block_rows=4,
                                   block_cols=64).sum()

    def lr(a, b, c):
        return fnm.rms_norm_matmul_composed(a, b, c).sum()

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(x, w, wm)
    gr = jax.jit(jax.grad(lr, argnums=(0, 1, 2)))(x, w, wm)
    for a, b in zip(gf, gr):
        assert (np.asarray(a) == np.asarray(b)).all()


# ------------------------------------------------- model-level selection


def test_llama_fused_paths_activate_from_cache(tmp_cache):
    """With tune-cache entries the llama forward routes through BOTH
    fusion kernels and stays numerically equivalent to the unfused
    forward; with no entries (the default) the unfused path runs."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(7)
    cfg = LlamaConfig.tiny()  # hidden 64, 4 heads, d=16, vocab 1000
    net = LlamaForCausalLM(cfg)
    net.eval()
    ids = Tensor(jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 32))))
    with paddle.no_grad():
        base = np.asarray(net(ids).numpy())

    at.get_cache().record(
        "rope_attention", at.rope_attention_sig(2, 32, 4, 16),
        {"block_q": 8}, save=False,
    )
    at.get_cache().record(
        "rms_norm_matmul", at.norm_matmul_sig(64, 64, cfg.vocab_size),
        {"block_rows": 8, "block_cols": 125},  # 1000 = 8 * 125
        save=False,
    )
    sel_before = at.selection_counter().series()
    with paddle.no_grad():
        fused = np.asarray(net(ids).numpy())
    sel_after = at.selection_counter().series()

    def _delta(kernel, path):
        k = (("kernel", kernel), ("path", path))
        return sel_after.get(k, 0) - sel_before.get(k, 0)

    assert _delta("rope_attention", "fused:cached") >= 1
    assert _delta("rms_norm_matmul", "fused:cached") >= 1
    np.testing.assert_allclose(fused, base, rtol=2e-4, atol=2e-4)


def test_measured_composed_win_is_not_installed(tmp_cache):
    """Review pin: the tuner must never install a measured performance
    regression. An entry whose fused_beats_composed verdict is False
    stays a cache hit (no re-measurement) but selection keeps the
    composed/unfused path; an entry WITHOUT the verdict (seeded,
    hand-written) still activates."""
    at.get_cache().record(
        "rms_norm_matmul", at.norm_matmul_sig(8, 32, 128),
        {"block_rows": 8, "block_cols": 128},
        extra={"fused_beats_composed": False}, save=False,
    )
    assert fnm.head_fusion_select(8, 32, 128) is None
    sel = at.selection_counter().series()
    assert sel.get((("kernel", "rms_norm_matmul"),
                    ("path", "composed:measured")), 0) >= 1

    at.get_cache().record(
        "rope_attention", at.rope_attention_sig(2, 64, 2, 16),
        {"block_q": 16}, extra={"fused_beats_composed": False},
        save=False,
    )
    assert fra.rope_attention_select(2, 64, 2, 16) is None

    at.get_cache().record(
        "rms_norm_matmul", at.norm_matmul_sig(16, 32, 128),
        {"block_rows": 8, "block_cols": 128}, save=False,
    )
    assert fnm.head_fusion_select(16, 32, 128) == {
        "block_rows": 8, "block_cols": 128}


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


def test_tune_shape_records_verdict(tmp_cache):
    """A constant injected clock makes every candidate tie, so fused
    does NOT beat composed: the recorded entry carries the verdict and
    selection refuses to activate the fused path."""
    import tools.kernel_tune as kt

    cache = at.TuneCache(tmp_cache)
    row = kt.tune_shape(
        "rms_norm_matmul", {"rows": 8, "hidden": 32, "n_out": 128},
        cache, iters=1, windows=1, clock=lambda: 0.0,
        sync=lambda x: None,
    )
    assert row["fused_beats_composed"] is False
    entry = cache.entry("rms_norm_matmul", at.norm_matmul_sig(8, 32, 128))
    assert entry["fused_beats_composed"] is False
    # the process-wide cache reads the same file the driver wrote
    assert fnm.head_fusion_select(8, 32, 128) is None


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


def test_rope_attention_tune_baseline_is_production_path(tmp_cache,
                                                        monkeypatch):
    """Review pin: the rope_attention fused-vs-composed verdict is
    measured against the real unfused path (rope kernel + flash
    attention SELECTION, which picks tuned pallas flash where eligible)
    — not against bare composed attention."""
    import tools.kernel_tune as kt
    from paddle_tpu.kernels import flash_attention as fa_mod

    calls = []
    real = fa_mod.flash_attention_fwd

    def spying(q, k, v, causal=False, scale=None):
        calls.append(q.shape)
        return real(q, k, v, causal=causal, scale=scale)

    monkeypatch.setattr(fa_mod, "flash_attention_fwd", spying)
    build = kt._build_factory("rope_attention",
                              {"b": 1, "s": 32, "h": 2, "d": 16})
    baseline = build({"path": "composed"})
    baseline()
    assert calls, "composed baseline did not route through " \
                  "flash_attention_fwd"


def test_run_tune_second_run_is_all_hits(tmp_cache):
    from tools.kernel_tune import run_tune

    specs = [("rms_norm_matmul", {"rows": 8, "hidden": 32, "n_out": 128})]
    rec = run_tune(cache_path=tmp_cache, specs=specs, iters=1, windows=1)
    assert rec["shapes_measured"] == 1 and rec["cache_hits"] == 0
    rec2 = run_tune(cache_path=tmp_cache, specs=specs, iters=1,
                    windows=1)
    assert rec2["shapes_measured"] == 0 and rec2["cache_hits"] == 1
    assert rec2["cache_hit_rate"] == 1.0


# ---------------------------------------------------- paged decode attention


def _paged_fixture(dtype=jnp.float32, kvh=2, h=4):
    from paddle_tpu.kernels import paged_attention as pa  # noqa: F401

    rng = np.random.RandomState(5)
    b, pages, ps, d = 2, 4, 8, 16
    n = b * pages + 1
    q = jnp.asarray(rng.randn(b, 1, h, d), dtype)
    kp = jnp.asarray(rng.randn(n, ps, kvh, d), dtype)
    vp = jnp.asarray(rng.randn(n, ps, kvh, d), dtype)
    tbl = jnp.asarray(1 + np.arange(b * pages).reshape(b, pages),
                      jnp.int32)
    pos = jnp.asarray([13, 27], jnp.int32)
    return q, kp, vp, tbl, pos


def test_paged_candidates_legal_and_sig():
    for cfg in at.paged_attention_candidates(8):
        assert at.paged_attention_config_legal(8, cfg), cfg
    # the chip takes a page block only at the whole kvH axis or a
    # multiple of 8 of it
    assert [c["block_kvh"] for c in at.paged_attention_candidates(8)] \
        == [8]
    assert [c["block_kvh"] for c in at.paged_attention_candidates(32)] \
        == [32, 16, 8]
    assert [c["block_kvh"] for c in at.paged_attention_candidates(4)] \
        == [4]
    assert not at.paged_attention_config_legal(8, {"block_kvh": 3})
    assert not at.paged_attention_config_legal(8, {"block_kvh": 4})
    # an int8 arena's scale block has the heads on its minor axis: only
    # the whole axis
    assert at.paged_attention_candidates(32, quant=True) \
        == [{"block_kvh": 32}]
    assert not at.paged_attention_config_legal(32, {"block_kvh": 8},
                                               quant=True)
    assert not at.paged_attention_config_legal(8, {})
    s = at.paged_attention_sig(2, 4, 8, 4, 2, 16)
    assert s == "b2_p4_ps8_h4_kv2_d16"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_bitexact_vs_reference(dtype):
    """The kernel contract: bit-identical to its blocked reference
    under jit, and invariant in the block_kvh tuning knob (GQA group
    repeat included)."""
    from paddle_tpu.kernels import paged_attention as pa

    q, kp, vp, tbl, pos = _paged_fixture(dtype)
    ref = jax.jit(lambda a, k_, v_: pa.paged_attention_reference(
        a, k_, v_, tbl, pos))(q, kp, vp)
    outs = [
        jax.jit(lambda a, k_, v_: pa.paged_attention_fused(
            a, k_, v_, tbl, pos, block_kvh=bk))(q, kp, vp)
        for bk in (1, 2, None)
    ]
    for out in outs:
        assert out.dtype == q.dtype
        assert (np.asarray(out, np.float32)
                == np.asarray(ref, np.float32)).all()
    # composed gather formulation agrees to float rounding (different
    # dot shapes -> different XLA microkernels; why engine activation
    # is opt-in, not default)
    comp = pa.paged_attention_composed(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(
        np.asarray(comp, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_paged_selection_cache_optin(tmp_cache):
    """No entry -> composed (counted); entry -> fused config; measured
    composed-win -> refused; stale/illegal entry -> signalled
    fallback."""
    from paddle_tpu.kernels import paged_attention as pa

    sig = at.paged_attention_sig(2, 4, 8, 4, 2, 16)
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16) is None

    at.get_cache().record("paged_attention", sig, {"block_kvh": 2},
                          save=False)
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16) == {
        "block_kvh": 2}
    sel = at.selection_counter().series()
    assert sel.get((("kernel", "paged_attention"),
                    ("path", "fused:cached")), 0) >= 1

    at.get_cache().record(
        "paged_attention", sig, {"block_kvh": 2},
        extra={"fused_beats_composed": False}, save=False,
    )
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16) is None
    sel = at.selection_counter().series()
    assert sel.get((("kernel", "paged_attention"),
                    ("path", "composed:measured")), 0) >= 1

    at.get_cache().record("paged_attention", sig, {"block_kvh": 3},
                          save=False)  # illegal for kvh=2
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16) is None
    fb = at.fallback_counter().series()
    assert any(
        dict(k).get("kernel") == "paged_attention"
        and dict(k).get("reason") == "stale-config"
        for k in fb
    )


def test_paged_entry_activates_llama_decode_path(tmp_cache):
    """Model-level: with a tune-cache entry for the engine's exact
    decode shape, the llama paged branch routes through the Pallas
    kernel (selection counted) and the decode logits stay numerically
    equivalent to the composed gather path."""
    import paddle_tpu as paddle
    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import alloc_kv_caches, prefill

    paddle.seed(3)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    rng = np.random.RandomState(1)
    B, L, ps, P = 2, 6, 8, 4
    ids = rng.randint(0, 64, (B, L)).astype(np.int32)
    N = B * P + 1
    arena = [
        (jnp.zeros((N, ps, cfg.kv_heads, cfg.head_dim), jnp.bfloat16),
         jnp.zeros((N, ps, cfg.kv_heads, cfg.head_dim), jnp.bfloat16))
        for _ in range(cfg.num_hidden_layers)
    ]
    tbl = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    for r in range(B):
        blk = alloc_kv_caches(cfg, 1, P * ps, "bfloat16")
        _, blk = prefill(net, jnp.asarray(ids[r:r + 1]), blk)
        for li, (kb, vb) in enumerate(blk):
            ka, va = arena[li]
            rows = np.asarray(tbl[r])
            ka = ka.at[rows].set(
                kb[0].reshape(P, ps, cfg.kv_heads, cfg.head_dim))
            va = va.at[rows].set(
                vb[0].reshape(P, ps, cfg.kv_heads, cfg.head_dim))
            arena[li] = (ka, va)
    tok = jnp.asarray(ids[:, -1])
    pos = jnp.full((B,), L, jnp.int32)

    def decode(caches):
        with tape.trace_scope(), tape.no_grad():
            lg, caches = net(Tensor(tok[:, None]), caches=caches,
                             pos=pos, page_table=tbl)
        return np.asarray(lg.value[:, -1, :], np.float32), caches

    base, _ = decode(arena)  # no entry: composed gather path
    at.get_cache().record(
        "paged_attention",
        at.paged_attention_sig(B, P, ps, cfg.num_attention_heads,
                               cfg.kv_heads, cfg.head_dim),
        {"block_kvh": cfg.kv_heads}, save=False,
    )
    sel_before = at.selection_counter().series()
    fused, _ = decode(arena)
    sel_after = at.selection_counter().series()
    k = (("kernel", "paged_attention"), ("path", "fused:cached"))
    assert sel_after.get(k, 0) - sel_before.get(k, 0) >= 1

    # an explicit attn_mask must bypass the fused kernel (it bakes in
    # pure positional masking) and take the composed path — with a
    # zeros mask the logits stay equal to the no-entry baseline
    def decode_masked(caches):
        am = jnp.zeros((B, 1, 1, P * ps), jnp.float32)
        with tape.trace_scope(), tape.no_grad():
            lg, caches = net(Tensor(tok[:, None]), attn_mask=Tensor(am),
                             caches=caches, pos=pos, page_table=tbl)
        return np.asarray(lg.value[:, -1, :], np.float32)

    sel_before = at.selection_counter().series()
    masked = decode_masked(arena)
    sel_after = at.selection_counter().series()
    assert sel_after.get(k, 0) == sel_before.get(k, 0)  # no fused pick
    np.testing.assert_array_equal(masked, base)
    np.testing.assert_allclose(fused, base, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ int8 matmul
def test_int8_matmul_candidates_and_sig():
    for cfg in at.int8_matmul_candidates(8, 256):
        assert at.int8_matmul_config_legal(8, 256, cfg), cfg
    assert not at.int8_matmul_config_legal(8, 256, {"block_rows": 3,
                                                    "block_cols": 128})
    assert at.int8_matmul_sig(8, 64, 256) == "r8_h64_n256"
    # the int8-KV paged flavor is its OWN tuning signature — a bf16
    # measurement must never activate the quantized kernel untested
    assert at.paged_attention_sig(2, 4, 8, 4, 2, 16, quant=True) \
        == "b2_p4_ps8_h4_kv2_d16_q8"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_fused_equals_composed(dtype):
    """The weight-only kernel contract: fused (dequant epilogue in
    VMEM) == composed (dequant then matmul) EXACTLY under jit, for
    every legal block config."""
    from paddle_tpu.kernels import int8_matmul as im

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 64), dtype)
    wq, sc = im.quantize_weight(
        jnp.asarray(rng.randn(64, 256), jnp.float32)
    )
    comp = jax.jit(lambda a: im.int8_matmul_composed(a, wq, sc))(x)
    assert comp.dtype == dtype
    for br, bc in ((8, 128), (16, 256), (4, 128)):
        fused = jax.jit(
            lambda a: im.int8_matmul(a, wq, sc, block_rows=br,
                                     block_cols=bc)
        )(x)
        assert (np.asarray(fused, np.float32)
                == np.asarray(comp, np.float32)).all(), (br, bc)
    # and the quantized product stays close to the exact dequantized
    # product (fp32 only — bf16 adds its own output rounding on top)
    if dtype == jnp.float32:
        wf = np.asarray(wq, np.float32) * np.asarray(sc)[None, :]
        ref = np.asarray(x, np.float32) @ wf
        np.testing.assert_allclose(np.asarray(comp, np.float32), ref,
                                   rtol=2e-2, atol=2e-2)


def test_int8_matmul_selection_cache_optin(tmp_cache):
    """Same discipline as every fused kernel: no entry -> composed;
    entry -> fused config; measured composed-win refused; stale entry
    is a counted fallback."""
    from paddle_tpu.kernels import int8_matmul as im

    sig = at.int8_matmul_sig(8, 64, 256)
    assert im.int8_matmul_select(8, 64, 256) is None

    at.get_cache().record("int8_matmul", sig,
                          {"block_rows": 8, "block_cols": 128},
                          save=False)
    assert im.int8_matmul_select(8, 64, 256) == {
        "block_rows": 8, "block_cols": 128}
    sel = at.selection_counter().series()
    assert sel.get((("kernel", "int8_matmul"),
                    ("path", "fused:cached")), 0) >= 1

    at.get_cache().record(
        "int8_matmul", sig, {"block_rows": 8, "block_cols": 128},
        extra={"fused_beats_composed": False}, save=False,
    )
    assert im.int8_matmul_select(8, 64, 256) is None

    at.get_cache().record("int8_matmul", sig,
                          {"block_rows": 3, "block_cols": 128},
                          save=False)  # illegal for rows=8
    assert im.int8_matmul_select(8, 64, 256) is None
    fb = at.fallback_counter().series()
    assert any(
        dict(k).get("kernel") == "int8_matmul"
        and dict(k).get("reason") == "stale-config"
        for k in fb
    )


def test_quantized_linear_activates_fused_from_cache(tmp_cache):
    """Model-level: a tune-cache entry for the QuantizedLinear's exact
    shape routes its forward through the fused kernel (selection
    counted) with output EXACTLY equal to the composed path."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.kernels import int8_matmul as im
    from paddle_tpu.quantization import QuantizedLinear

    rng = np.random.RandomState(2)
    wq, sc = im.quantize_weight(
        jnp.asarray(rng.randn(64, 256), jnp.float32)
    )
    lin = QuantizedLinear(wq, sc)
    x = Tensor(jnp.asarray(rng.randn(8, 64), jnp.float32))
    base = np.asarray(lin(x).numpy())
    at.get_cache().record(
        "int8_matmul", at.int8_matmul_sig(8, 64, 256),
        {"block_rows": 8, "block_cols": 128}, save=False,
    )
    sel_before = at.selection_counter().series()
    fused = np.asarray(lin(x).numpy())
    sel_after = at.selection_counter().series()
    k = (("kernel", "int8_matmul"), ("path", "fused:cached"))
    assert sel_after.get(k, 0) - sel_before.get(k, 0) >= 1
    np.testing.assert_array_equal(fused, base)


# --------------------------------------------------------- int8 paged KV
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_paged_kernel_bitexact_vs_reference(dtype):
    """Int8-arena flavor of the kernel contract: bit-identical to the
    blocked dequant reference under jit, knob-invariant, and the
    composed dequant-on-gather agrees to float rounding."""
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.quantization.kv import QuantizedKV, quantize_kv

    q, kp, vp, tbl, pos = _paged_fixture(dtype)
    kq = QuantizedKV(*quantize_kv(kp))
    vq = QuantizedKV(*quantize_kv(vp))
    ref = jax.jit(lambda a, k_, v_: pa.paged_attention_reference(
        a, k_, v_, tbl, pos))(q, kq, vq)
    for bk in (1, 2):
        out = jax.jit(lambda a, k_, v_: pa.paged_attention_fused(
            a, k_, v_, tbl, pos, block_kvh=bk))(q, kq, vq)
        assert out.dtype == q.dtype
        assert (np.asarray(out, np.float32)
                == np.asarray(ref, np.float32)).all(), bk
    comp = pa.paged_attention_composed(q, kq, vq, tbl, pos)
    np.testing.assert_allclose(
        np.asarray(comp, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_int8_paged_selection_keyed_by_quant_sig(tmp_cache):
    """A bf16 entry for the shape must NOT activate the int8 kernel
    (and vice versa): the quantized flavor selects only under its own
    ``_q8`` signature."""
    from paddle_tpu.kernels import paged_attention as pa

    at.get_cache().record(
        "paged_attention", at.paged_attention_sig(2, 4, 8, 4, 2, 16),
        {"block_kvh": 2}, save=False,
    )
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16) is not None
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16,
                                     quantized=True) is None
    at.get_cache().record(
        "paged_attention",
        at.paged_attention_sig(2, 4, 8, 4, 2, 16, quant=True),
        {"block_kvh": 2}, save=False,
    )
    assert pa.paged_attention_select(2, 4, 8, 4, 2, 16,
                                     quantized=True) == {"block_kvh": 2}
