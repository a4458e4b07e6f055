"""Flash-attention path selection (kernels/flash_attention.py).

The policy is measurement-driven (BENCH_NOTES round-5 ablation): tuned
pallas for causal S>=2048 or any >2GiB score matrix, composed
otherwise. These tests pin the decision logic and the v5e block
clamping on CPU (the kernels themselves are exercised on the chip).
"""
import numpy as np
import pytest

import paddle_tpu.kernels.flash_attention as fa


def _qkv(b, s, h, d):
    x = np.zeros((b, s, h, d), np.float32)
    return x, x, x


def test_selection_causal_threshold(force_tpu):
    q, k, v = _qkv(4, 1024, 16, 128)
    assert not fa._pallas_ok(q, k, v, causal=True)  # flagship stays composed
    q, k, v = _qkv(4, 2048, 16, 128)
    assert fa._pallas_ok(q, k, v, causal=True)
    assert not fa._pallas_ok(q, k, v, causal=False)  # no triangle to skip


def test_selection_memory_threshold_non_causal(force_tpu):
    # 4*B*H*S^2 > 2 GiB -> pallas even without causality
    q, k, v = _qkv(8, 8192, 16, 128)
    assert fa._pallas_ok(q, k, v, causal=False)


def test_selection_shape_constraints(force_tpu):
    q, k, v = _qkv(4, 2048 + 2, 16, 128)  # not a lane multiple
    assert not fa._pallas_ok(q, k, v, causal=True)
    q, k, v = _qkv(4, 2048, 16, 96)  # unsupported head_dim
    assert not fa._pallas_ok(q, k, v, causal=True)
    # multiples of the tuned blocks are accepted (3072 = 6*512 = 3*1024)
    q, k, v = _qkv(4, 3072, 16, 128)
    assert fa._pallas_ok(q, k, v, causal=True)


def test_indivisible_seed_two_regime_policy(force_tpu):
    """2176 = 17*128 fails the seeded blocks' modulo checks. In the
    time regime an unmeasured generated config is NOT trusted
    (BENCH_NOTES measured small-block pallas up to 2.5x slower than
    composed): composed is kept and the shape is SIGNALLED for tuning
    instead of silently losing (the pre-autotuner failure mode). In
    the memory regime (>2 GiB fp32 scores) the divisibility-aware
    generator's legal config is used — any legal pallas config beats
    materializing the O(S^2) scores."""
    from paddle_tpu.kernels import autotune

    autotune.reset_warned()
    q, k, v = _qkv(4, 2176, 16, 128)  # score matrix ~1.2 GiB: time regime
    with pytest.warns(RuntimeWarning, match="untuned-config"):
        ok, cfg, reason = fa._select(q, k, v, causal=True)
    assert not ok and reason == "fallback:untuned-config"
    q, k, v = _qkv(8, 2176, 32, 128)  # ~4.5 GiB scores: memory regime
    ok, cfg, reason = fa._select(q, k, v, causal=True)
    assert ok and reason == "pallas:generated"
    assert autotune.flash_config_legal(2176, 2176, cfg)
    bs = fa._tuned_block_sizes(2176, 2176, config=cfg)
    assert 2176 % bs.block_q == 0 and 2176 % bs.block_k_major == 0


def test_selection_off_on_cpu():
    q, k, v = _qkv(4, 4096, 16, 128)
    assert not fa._pallas_ok(q, k, v, causal=True)  # CPU CI: composed


def test_tuned_blocks_clamp_short_seqs():
    bs = fa._tuned_block_sizes(256, 256)
    assert bs.block_q == 256 and bs.block_k_major == 256
    bs = fa._tuned_block_sizes(4096, 4096)
    assert (bs.block_q, bs.block_k_major, bs.block_k) == (512, 1024, 512)
    assert bs.block_q_dkv == 512 and bs.block_k_major_dq == 1024


def test_tuned_blocks_prefer_cache_entry(tmp_path, monkeypatch):
    """Acceptance pin: with no cache entry _tuned_block_sizes is the
    seeded v5e default (byte-identical selection); with an entry it
    returns the cached config."""
    from paddle_tpu.kernels import autotune

    monkeypatch.setenv(autotune.ENV_CACHE,
                       str(tmp_path / "tune_cache.json"))
    autotune.reset_cache()
    bs = fa._tuned_block_sizes(2048, 2048, b=4, h=16, d=128)
    assert (bs.block_q, bs.block_k_major, bs.block_k) == (512, 1024, 512)
    autotune.get_cache().record(
        "flash_attention", autotune.flash_sig(4, 2048, 2048, 16, 128, True),
        {"block_q": 256, "block_k_major": 512, "block_k": 256},
    )
    bs = fa._tuned_block_sizes(2048, 2048, b=4, h=16, d=128)
    assert (bs.block_q, bs.block_k_major, bs.block_k) == (256, 512, 256)
    autotune.reset_cache()


# What flash selects at the shapes the benchmark's cells run
# (BENCHMARK.json), q and k/v as ``[B, S, H, D]``; the cells' cache
# holds no entry for any of them, so a Pallas pick is the seeded triple.
CELL_SHAPES = {
    "mistral train b2 s2048 h32 d128": (
        (2, 2048, 32, 128), (2, 2048, 32, 128), None, "pallas:seed"),
    "the same under the dp2 x mp2 mesh": (
        (2, 2048, 32, 128), (2, 2048, 32, 128), (2, 2),
        "fallback:unpartitionable"),
    "xing4 prefill b1 s4096 h32 d256": (
        (1, 4096, 32, 256), (1, 4096, 32, 256), None, "pallas:seed"),
    "a 256-token prefill bucket": (
        (1, 256, 32, 128), (1, 256, 32, 128), None,
        "policy:below-threshold"),
    "one-token decode over 4096": (
        (32, 1, 32, 128), (32, 4096, 32, 128), None,
        "policy:cross-length-causal"),
}


@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_selection_at_the_cells_shapes(force_tpu, cell):
    import warnings

    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import mesh as mesh_mod

    q_shape, k_shape, mesh, want = CELL_SHAPES[cell]
    q, k = np.zeros(q_shape, np.float32), np.zeros(k_shape, np.float32)
    if mesh is not None:
        devs = np.array(jax.local_devices()[:4]).reshape(mesh)
        mesh_mod.set_mesh(Mesh(devs, ("dp", "mp")))
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            use, cfg, reason = fa._select(q, k, k, causal=True)
    finally:
        mesh_mod.set_mesh(None)
    assert reason == want
    assert use == want.startswith("pallas:")
    if use:
        assert cfg == fa._seed_config(q_shape[1], k_shape[1])
