"""kernel_tune — measured-search block-config tuning for the Pallas kernels.

Drives ``paddle_tpu.kernels.autotune`` over the one Pallas kernel with
block sizes to choose, flash attention, and over the fp8 train matmul
(AMP O3; no block sizes, the fp8-vs-bf16 verdict alone), and records the
winners in the persistent tune cache (``tools/kernel_tune_cache.json``
by default, checked in for v5e like the lint baseline;
``PADDLE_TPU_TUNE_CACHE`` overrides).

    python tools/kernel_tune.py              # tune this device's standard shapes
    python tools/kernel_tune.py --json       # machine-readable report
    python tools/kernel_tune.py --smoke      # CPU-safe machinery gate (CI)
    python tools/kernel_tune.py --cache P    # explicit cache file

Methodology (BENCH_NOTES r5, the hand ablation this generalizes): every
candidate — including the composed-reference baseline — is timed
fwd+bwd in interleaved round-robin windows and compared by
median-of-windows, so one contended window cannot poison a single
candidate. A shape with a cache entry is a HIT: zero measurements, the
entry is reported as-is (re-tune by deleting the entry or pointing
``--cache`` elsewhere).

``--smoke`` is the ``make tune-smoke`` gate: a tiny shape, CPU-safe (the
stock flash kernel needs a chip and is skipped there), a throwaway
cache file. It asserts candidate-generator legality, a cache write/read
round trip and a 100%-cache-hit re-run with zero re-measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _on_tpu():
    from paddle_tpu.kernels import autotune

    return not autotune.interpret_mode()


# --------------------------------------------------------- shape catalogs


def standard_specs(on_tpu):
    """(kernel, spec) list for this backend. TPU: the flagship
    llama-748M geometry (B=4, H=16, D=128, hidden 2048) at the train S
    and the long-context S values BENCH_NOTES measured. CPU: one tiny
    shape (a smoke of the machinery, not a performance measurement)."""
    if on_tpu:
        return [
            ("flash_attention",
             {"b": 4, "s": 2048, "h": 16, "d": 128, "causal": True}),
            ("flash_attention",
             {"b": 4, "s": 4096, "h": 16, "d": 128, "causal": True}),
            # fp8 train matmul (AMP O3): the flagship gemm shapes —
            # records the measured fp8-vs-bf16 verdict for the device
            ("fp8_matmul", {"m": 4096, "k": 2048, "n": 8192}),
            ("fp8_matmul", {"m": 4096, "k": 2048, "n": 2048}),
        ]
    return [("fp8_matmul", {"m": 16, "k": 64, "n": 128})]


# ------------------------------------------------------------ tune drivers


def _sig_and_candidates(kernel, spec):
    from paddle_tpu.kernels import autotune

    if kernel == "flash_attention":
        sig = autotune.flash_sig(spec["b"], spec["s"], spec["s"],
                                 spec["h"], spec["d"], spec["causal"])
        cands = autotune.flash_block_candidates(spec["s"], spec["s"])
    elif kernel == "fp8_matmul":
        sig = autotune.fp8_matmul_sig(spec["m"], spec["k"], spec["n"])
        cands = autotune.fp8_matmul_candidates()
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return sig, cands


def _build_factory(kernel, spec):
    """build(config) -> zero-arg fwd+bwd runnable for the candidate.
    ``{"path": "composed"}`` builds the composed-reference baseline."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    dtype = jnp.bfloat16 if _on_tpu() else jnp.float32

    if kernel == "flash_attention":
        from paddle_tpu.kernels import flash_attention as fa

        b, s, h, d = spec["b"], spec["s"], spec["h"], spec["d"]
        causal = spec.get("causal", True)
        q = jnp.asarray(rng.randn(b, s, h, d), dtype)
        k = jnp.asarray(rng.randn(b, s, h, d), dtype)
        v = jnp.asarray(rng.randn(b, s, h, d), dtype)

        def build(config):
            if config.get("path") == "composed":
                def f(qv, kv, vv):
                    return fa._composed(
                        qv, kv, vv, causal=causal,
                        scale=1.0 / float(np.sqrt(d)),
                    ).astype(jnp.float32).sum()
            else:
                pallas_fa = fa._pallas_fa()
                bs = fa._tuned_block_sizes(s, s, config=config)

                def f(qv, kv, vv):
                    out = pallas_fa(
                        jnp.swapaxes(qv, 1, 2),
                        jnp.swapaxes(kv, 1, 2),
                        jnp.swapaxes(vv, 1, 2),
                        causal=causal,
                        sm_scale=1.0 / float(np.sqrt(d)),
                        block_sizes=bs,
                    )
                    return out.astype(jnp.float32).sum()

            step = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
            return lambda: step(q, k, v)

        return build

    if kernel == "fp8_matmul":
        from paddle_tpu.amp import fp8 as fp8_mod

        m, kk, n = spec["m"], spec["k"], spec["n"]
        x = jnp.asarray(rng.randn(m, kk), dtype)
        w = jnp.asarray(rng.randn(kk, n), dtype)
        sx = jnp.float32(1.0)
        sw = jnp.float32(1.0)
        xname = jnp.dtype(x.dtype).name
        wname = jnp.dtype(w.dtype).name

        def build(config):
            # the O3 unit: fwd + bwd through the e4m3/e5m2 custom VJP
            # vs the production bf16/fp32 dot it would replace
            if config.get("path") == "composed":
                def f(xv, wv):
                    return jnp.dot(xv, wv).astype(jnp.float32).sum()
            else:
                def f(xv, wv):
                    return fp8_mod._fp8_dot(
                        xname, wname, xv, wv, sx, sw
                    ).astype(jnp.float32).sum()

            step = jax.jit(jax.grad(f, argnums=(0, 1)))
            return lambda: step(x, w)

        return build

    raise ValueError(f"unknown kernel {kernel!r}")


def tune_shape(kernel, spec, cache, *, iters=3, windows=3,
               max_candidates=24, clock=None, sync=None):
    """Cache-or-measure one (kernel, spec). Returns a report row."""
    from paddle_tpu.kernels import autotune

    sig, cands = _sig_and_candidates(kernel, spec)
    row = {"kernel": kernel, "sig": sig, "spec": spec}
    hit = cache.lookup(kernel, sig)
    if hit is not None:
        row.update(cache_hit=True, config=hit, measured=0)
        return row
    if not cands:
        row.update(cache_hit=False, config=None, measured=0,
                   reason="no-legal-candidates")
        return row
    if kernel == "flash_attention":
        from paddle_tpu.kernels import flash_attention as _fa

        if not _on_tpu() or _fa._pallas_fa() is None:
            # the stock pallas flash kernel has no interpret path —
            # tuning it needs a chip (+ the jax tpu ops lib)
            row.update(cache_hit=False, config=None, measured=0,
                       reason="requires-tpu")
            return row
    if len(cands) > max_candidates:
        row["truncated_candidates"] = len(cands) - max_candidates
        cands = cands[:max_candidates]
    cands = [{"path": "composed"}] + cands
    build = _build_factory(kernel, spec)
    best, table = autotune.measured_search(
        cands, build, iters=iters, windows=windows, clock=clock,
        sync=sync,
    )
    pallas_rows = [r for r in table
                   if r["config"].get("path") != "composed"]
    composed = next((r for r in table
                     if r["config"].get("path") == "composed"), None)
    winner = pallas_rows[0]["config"] if pallas_rows else None
    fused_wins = (composed is not None and bool(pallas_rows)
                  and pallas_rows[0]["median_s"] < composed["median_s"])
    if winner is not None:
        # record the best fused config EITHER WAY (so a re-run is a
        # cache hit, not a re-measurement), but store the measured
        # fused-vs-composed verdict with it: flash's ``_select`` keeps
        # composed, in the time regime, where the entry says
        # fused_beats_composed is False — the tuner must never install
        # a measured performance regression.
        timings = {json.dumps(r["config"], sort_keys=True):
                   round(r["median_s"] * 1e3, 4) for r in table}
        cache.record(kernel, sig, winner, timings_ms=timings,
                     extra={"fused_beats_composed": fused_wins})
    row.update(
        cache_hit=False, config=winner, measured=len(table),
        table=[{"config": r["config"],
                "median_ms": round(r["median_s"] * 1e3, 4)}
               for r in table],
        composed_median_ms=(round(composed["median_s"] * 1e3, 4)
                            if composed else None),
        fused_beats_composed=fused_wins,
    )
    return row


def run_tune(cache_path=None, specs=None, *, iters=3, windows=3,
             clock=None, sync=None):
    """Tune every spec (default: this backend's standard catalog);
    returns the self-describing record bench.py --tune emits."""
    import jax

    from paddle_tpu.kernels import autotune

    cache = (autotune.TuneCache(cache_path) if cache_path
             else autotune.get_cache())
    redirected = False
    if (not cache_path and not _on_tpu()
            and cache.path == autotune.DEFAULT_CACHE_PATH):
        # a chipless dev-box run must NOT dirty the checked-in v5e
        # baseline artifact: divert default-path writes to a per-user
        # scratch file (still persistent, so a CPU re-run is a cache
        # hit). An explicit --cache / PADDLE_TPU_TUNE_CACHE wins.
        uid = getattr(os, "getuid", lambda: 0)()
        cache = autotune.TuneCache(os.path.join(
            tempfile.gettempdir(),
            f"paddle_tpu_kernel_tune_cpu_{uid}.json"))
        redirected = True
    specs = specs if specs is not None else standard_specs(_on_tpu())
    rows = [tune_shape(kernel, spec, cache, iters=iters, windows=windows,
                       clock=clock, sync=sync)
            for kernel, spec in specs]
    measured = sum(1 for r in rows if r["measured"])
    hits = sum(1 for r in rows if r.get("cache_hit"))
    d = jax.devices()[0]
    return {
        "metric": "kernel_tune",
        "device": autotune.device_kind(),
        "platform": d.platform,
        "cache_path": cache.path,
        "cache_redirected_from": (autotune.DEFAULT_CACHE_PATH
                                  if redirected else None),
        "iters_per_window": iters,
        "windows": windows,
        "shapes": len(rows),
        "shapes_measured": measured,
        "cache_hits": hits,
        "cache_hit_rate": round(hits / len(rows), 4) if rows else None,
        "results": rows,
    }


# ------------------------------------------------------------------- smoke


def smoke():
    """CPU-safe machinery gate (``make tune-smoke``)."""
    from paddle_tpu.kernels import autotune

    # 1. candidate generators: every emitted config is legal; shapes
    # with no MXU-friendly divisor yield empty (-> signalled fallback)
    for cfg in autotune.flash_block_candidates(2048, 2048):
        assert autotune.flash_config_legal(2048, 2048, cfg), cfg
    for cfg in autotune.flash_block_candidates(2176, 2176):
        assert autotune.flash_config_legal(2176, 2176, cfg), cfg
    assert autotune.flash_block_candidates(2050, 2050) == []
    assert autotune.fp8_matmul_candidates() == [{"format": "e4m3"}]

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "tune_cache.json")
        # 2. measured search over the tiny CPU spec writes the cache
        # (catalog pinned to the CPU one so the step-3 verification
        # below matches even when the smoke runs on a TPU host)
        smoke_specs = standard_specs(False)
        rec = run_tune(cache_path=path, specs=smoke_specs,
                       iters=1, windows=1)
        assert rec["shapes_measured"] == rec["shapes"] > 0, rec
        assert os.path.exists(path), "cache file not written"

        # 3. a FRESH cache object reads the entries back
        cache = autotune.TuneCache(path)
        keys = cache.keys()
        assert len(keys) == rec["shapes"], (keys, rec["shapes"])
        for kernel, spec in smoke_specs:
            sig, _ = _sig_and_candidates(kernel, spec)
            cfg = cache.lookup(kernel, sig, count=False)
            assert cfg is not None, f"no entry for {kernel}|{sig}"
            assert cfg.get("format") == "e4m3", cfg

        # 4. second run: 100% cache hits, zero re-measurements
        rec2 = run_tune(cache_path=path, specs=smoke_specs,
                        iters=1, windows=1)
        assert rec2["cache_hits"] == rec2["shapes"], rec2
        assert rec2["shapes_measured"] == 0, rec2

    print("tune-smoke OK: generators legal, cache round-trips, "
          "re-run is 100% hits with 0 measurements")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-safe machinery gate (make tune-smoke)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: PADDLE_TPU_TUNE_CACHE or "
                         "tools/kernel_tune_cache.json)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--windows", type=int, default=3)
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    rec = run_tune(cache_path=args.cache, iters=args.iters,
                   windows=args.windows)
    if args.json:
        print(json.dumps(rec, indent=1))
    else:
        for row in rec["results"]:
            state = ("HIT " if row.get("cache_hit")
                     else "SKIP" if row["config"] is None else "TUNE")
            extra = ""
            if row.get("composed_median_ms") is not None:
                extra = (f"  composed={row['composed_median_ms']}ms "
                         f"fused_wins={row['fused_beats_composed']}")
            print(f"{state} {row['kernel']}|{row['sig']} -> "
                  f"{row['config']}{extra}")
        print(f"{rec['shapes']} shape(s): {rec['cache_hits']} cache "
              f"hit(s), {rec['shapes_measured']} measured "
              f"(cache: {rec['cache_path']})")
    return 0


if __name__ == "__main__":
    from paddle_tpu.jit import place_compile_cache

    place_compile_cache()
    sys.exit(main())
