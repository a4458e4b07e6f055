"""Measured-search block-config autotuner for the Pallas kernels.

BENCH_NOTES proved the principle by hand: re-tuning the flash-attention
block sizes (8x128 defaults -> bq512/bkm1024/bk512 on v5e) flipped
"pallas always loses" into a 1.5x win at S=4096. This module generalizes
that one-off into infrastructure, in the spirit of CUDA-L2's
measured-search-over-schedules (PAPERS.md):

- **Keys.** Results are stored per ``(kernel, shape-signature,
  device-kind)``. Shape signatures are canonical strings built by the
  per-kernel helpers below (``flash_sig``, ``fp8_matmul_sig``); device
  kinds are normalized
  (``jax.devices()[0].device_kind`` lowercased, spaces -> dashes, known
  aliases folded: a v5e chip reports "TPU v5 lite").
- **Measurement.** :func:`measured_search` times every candidate with
  the interleaved-median methodology the BENCH_NOTES r5 flash ablation
  validated: candidates are timed round-robin window by window (A/B/A/B
  ...), so a transient host slowdown hits every candidate equally
  instead of poisoning whichever one it landed on; the per-candidate
  number is the median across windows. The clock and the device-sync
  hook are injectable, so tests drive the whole search with a fake
  timer and zero wall-time dependence.
- **Persistence.** A JSON results cache (``tools/kernel_tune_cache.json``
  by default — checked in for v5e like the lint baseline; override with
  ``PADDLE_TPU_TUNE_CACHE``) fronted by an in-process memo. A corrupt or
  unreadable cache file degrades to "no entries" (callers fall back to
  their seeded defaults) and is counted, never raised.
- **Observability.** Selection decisions (pallas-vs-composed, cache
  hit/miss, fallback reason) publish ``paddle_kernels_*`` counters
  through the observability registry; a capability fallback additionally
  emits ONE warning per (kernel, signature, reason) and a
  flight-recorder event, so a long-context shape silently losing its
  1.5x win (the pre-autotuner failure mode) is impossible.

Candidate generation is divisibility-aware: generators only emit
configs every block of which divides the sequence/row extent it tiles,
so a shape that fails the seeded default's modulo checks gets a LEGAL
config instead of a silent composed fallback.
"""
from __future__ import annotations

import json
import os
import threading
import warnings

# ------------------------------------------------------------------ keys

# device_kind strings seen in the wild, folded to one canonical name so
# a cache tuned on one v5e host is valid on every v5e host
_DEVICE_ALIASES = {
    "tpu-v5-lite": "tpu-v5e",
    "tpu-v5lite": "tpu-v5e",
    "tpu-v5litepod": "tpu-v5e",
}


def normalize_device_kind(kind):
    k = str(kind).strip().lower().replace(" ", "-").replace("_", "-")
    return _DEVICE_ALIASES.get(k, k)


def device_kind():
    """Canonical device kind of the default backend ("cpu" off-chip)."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return "cpu"
    return normalize_device_kind(getattr(d, "device_kind", d.platform))


def interpret_mode():
    """Whether pallas kernels must run in interpret mode (no real
    accelerator backend). Single home for every kernel module's gate."""
    import jax

    return all(d.platform == "cpu" for d in jax.devices())


def spmd_refusal(kernel):
    """Whether ``kernel`` must give way to its composed path because
    the program it is traced into will be partitioned by GSPMD: a
    multi-device mesh is installed, and the chip's compiler refuses a
    compiled Pallas kernel there ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map.").
    Counted and warned once through :func:`note_fallback`. Interpreted
    kernels are plain jax ops and partition like any other, so the CPU
    runs keep them."""
    if interpret_mode():
        return False
    from ..parallel import mesh as mesh_mod

    if not mesh_mod.mesh_defined() or mesh_mod.get_mesh().size == 1:
        return False
    mesh = mesh_mod.get_mesh()
    note_fallback(
        kernel, "mesh" + "x".join(str(n) for n in mesh.devices.shape),
        "unpartitionable",
        detail="Mosaic kernels cannot be automatically partitioned. "
               "Please wrap the call in a shard_map.",
    )
    return True


def flash_sig(b, sq, sk, h, d, causal):
    return f"b{b}_sq{sq}_sk{sk}_h{h}_d{d}_c{int(bool(causal))}"


def fp8_matmul_sig(m, k, n):
    """fp8 train matmul (AMP O3): [m, k] x [k, n], e4m3 operands with
    per-tensor scaling, fp32 accumulate."""
    return f"m{m}_k{k}_n{n}"


def cache_key(kernel, sig, device=None):
    return f"{kernel}|{sig}|{device or device_kind()}"


# ------------------------------------------------------------- observability


def _registry():
    from ..observability import get_registry

    return get_registry()


def selection_counter():
    return _registry().counter(
        "paddle_kernels_selection_total",
        help="kernel path selections at trace time, by kernel and path",
    )


def fallback_counter():
    return _registry().counter(
        "paddle_kernels_fallback_total",
        help="capability fallbacks to the composed path (a wanted fused "
             "kernel could not run), by kernel and reason",
    )


def cache_counter():
    return _registry().counter(
        "paddle_kernels_tune_cache_total",
        help="tune-cache lookups and writes, by event "
             "(hit/miss/corrupt/write)",
    )


def tune_error_counter():
    return _registry().counter(
        "paddle_kernels_tune_candidate_errors_total",
        help="tune candidates skipped because build/warmup raised "
             "(Mosaic rejection, VMEM overflow), by kernel",
    )


def note_selection(kernel, path):
    """Count a selection decision (path: pallas/fused/composed)."""
    selection_counter().inc(kernel=kernel, path=path)


_WARNED = set()
_WARNED_LOCK = threading.Lock()


def note_fallback(kernel, sig, reason, detail=""):
    """A WANTED fused path could not run: metric + one-shot warning +
    flight-recorder event. Never raises (telemetry must not fail a
    step)."""
    fallback_counter().inc(kernel=kernel, reason=reason)
    key = (kernel, sig, reason)
    with _WARNED_LOCK:
        first = key not in _WARNED
        if first:
            _WARNED.add(key)
    if first:
        warnings.warn(
            f"paddle_tpu.kernels: {kernel} did not take the tuned "
            f"fused path for shape {sig} (reason: {reason}"
            + (f", {detail}" if detail else "")
            + "); run tools/kernel_tune.py to measure a config or see "
            "paddle_kernels_fallback_total for occurrence counts",
            RuntimeWarning, stacklevel=3,
        )
        try:
            from ..observability import get_flight_recorder

            get_flight_recorder().note(
                "kernel_fallback", kernel=kernel, sig=sig, reason=reason,
                detail=detail,
            )
        except Exception:
            pass


def reset_warned():
    """Test hook: re-arm the one-shot fallback warnings."""
    with _WARNED_LOCK:
        _WARNED.clear()


# ------------------------------------------------------------------- cache

ENV_CACHE = "PADDLE_TPU_TUNE_CACHE"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_PATH = os.path.join(_REPO, "tools", "kernel_tune_cache.json")
CACHE_VERSION = 1


def default_cache_path():
    return os.environ.get(ENV_CACHE) or DEFAULT_CACHE_PATH


class TuneCache:
    """Persistent JSON result cache with an in-process memo.

    File schema::

        {"version": 1,
         "entries": {"<kernel>|<sig>|<device>": {
             "config": {...block sizes...},
             "source": "seed-..."|"measured",
             "timings_ms": {...}            # optional, per candidate
         }}}

    A corrupt file (truncated write, hand-edit gone wrong) is treated as
    empty — callers fall back to their seeded defaults — and counted in
    ``paddle_kernels_tune_cache_total{event="corrupt"}``.
    """

    def __init__(self, path=None):
        self.path = path or default_cache_path()
        self._lock = threading.RLock()
        self._entries = None  # lazy: key -> entry dict
        self.corrupt = False

    # -- load/save ----------------------------------------------------
    def _load(self):
        if self._entries is not None:
            return self._entries
        entries = {}
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError("cache root is not an object")
            raw = data.get("entries", {})
            if not isinstance(raw, dict):
                raise ValueError("cache 'entries' is not an object")
            for k, v in raw.items():
                if isinstance(v, dict) and isinstance(v.get("config"), dict):
                    entries[k] = v
        except FileNotFoundError:
            pass
        except Exception:
            # corrupt cache: degrade to seeded defaults, loudly countable
            self.corrupt = True
            cache_counter().inc(event="corrupt")
            entries = {}
        self._entries = entries
        return entries

    def save(self):
        with self._lock:
            entries = dict(self._load())
        payload = {
            "version": CACHE_VERSION,
            "note": "kernel block-size autotuner results "
                    "(tools/kernel_tune.py; paddle_tpu/kernels/autotune.py)."
                    " Keys are kernel|shape_sig|device_kind.",
            "entries": {k: entries[k] for k in sorted(entries)},
        }
        tmp = self.path + ".tmp"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        cache_counter().inc(event="write")

    # -- lookup/record ------------------------------------------------
    def lookup(self, kernel, sig, device=None, count=True):
        """Config dict for (kernel, sig, device) or None. Counts
        hit/miss in the registry unless ``count=False``."""
        key = cache_key(kernel, sig, device)
        with self._lock:
            entry = self._load().get(key)
        if count:
            cache_counter().inc(event="hit" if entry else "miss",
                                kernel=kernel)
        return dict(entry["config"]) if entry else None

    def entry(self, kernel, sig, device=None):
        with self._lock:
            e = self._load().get(cache_key(kernel, sig, device))
        return dict(e) if e else None

    def record(self, kernel, sig, config, device=None, source="measured",
               timings_ms=None, extra=None, save=True):
        key = cache_key(kernel, sig, device)
        entry = {"config": dict(config), "source": source}
        if timings_ms:
            entry["timings_ms"] = timings_ms
        if extra:
            entry.update(extra)
        with self._lock:
            self._load()[key] = entry
            if save:
                self.save()
        return entry

    def keys(self):
        with self._lock:
            return sorted(self._load())


_CACHE = None
_CACHE_LOCK = threading.Lock()


def get_cache() -> TuneCache:
    """The process-wide cache for ``default_cache_path()``. Re-resolved
    when the path changes (tests flip ``PADDLE_TPU_TUNE_CACHE``)."""
    global _CACHE
    path = default_cache_path()
    with _CACHE_LOCK:
        if _CACHE is None or _CACHE.path != path:
            _CACHE = TuneCache(path)
        return _CACHE


def reset_cache():
    """Test hook: drop the in-process memo so the next lookup re-reads
    the cache file."""
    global _CACHE
    with _CACHE_LOCK:
        _CACHE = None


def lookup(kernel, sig, device=None):
    return get_cache().lookup(kernel, sig, device)


def lookup_entry(kernel, sig, device=None):
    """Full cache entry (config + metadata like the tuner's
    ``fused_beats_composed`` verdict) or None; counts hit/miss like
    :func:`lookup`."""
    entry = get_cache().entry(kernel, sig, device)
    cache_counter().inc(event="hit" if entry else "miss", kernel=kernel)
    return entry


# -------------------------------------------------------- candidate configs


def _divisors(n, options):
    return [b for b in options if b <= n and n % b == 0]


def flash_block_candidates(sq, sk):
    """Divisibility-aware (block_q, block_k_major, block_k) candidates
    for the stock Pallas flash kernel. Every candidate is LEGAL for
    (sq, sk): each block divides the extent it tiles and block_k divides
    block_k_major. Ordered largest-first (the measured v5e optimum sits
    at the large end; when used as an untuned fallback the first entry
    is taken). Empty when sq or sk has no MXU-friendly divisor."""
    qs = _divisors(sq, (1024, 512, 256, 128))
    kms = _divisors(sk, (1024, 512, 256, 128))
    out = []
    for bq in qs:
        for bkm in kms:
            for bk in (1024, 512, 256, 128):
                if bk <= bkm and bkm % bk == 0 and sk % bk == 0:
                    out.append({"block_q": bq, "block_k_major": bkm,
                                "block_k": bk})
    return out


def flash_config_legal(sq, sk, config):
    """The stock kernel asserts divisibility by its ACTUAL block sizes
    on both the q and kv sides (fwd and both backward passes use the
    same triple here)."""
    try:
        bq = int(config["block_q"])
        bkm = int(config["block_k_major"])
        bk = int(config["block_k"])
    except (KeyError, TypeError, ValueError):
        return False
    if min(bq, bkm, bk) < 1 or bk > bkm:
        return False
    return sq % bq == 0 and sk % bkm == 0 and sk % bk == 0 and bkm % bk == 0


def fp8_matmul_candidates(m=None, k=None, n=None):
    """The fp8 train-matmul path has no block-size knob (XLA owns the
    tiling of a plain fp8 dot); the single candidate exists so the
    tuner can record the measured fp8-vs-bf16 verdict for the shape."""
    return [{"format": "e4m3"}]


# ---------------------------------------------------------- measured search


def _default_sync(x):
    import jax

    jax.block_until_ready(x)


def measured_search(candidates, build, *, iters=3, windows=3, warmup=1,
                    clock=None, sync=None):
    """Interleaved-median search over ``candidates``.

    ``build(config) -> callable`` returns a zero-arg runnable for the
    candidate (compile happens in warmup, outside the timed windows).
    Within each window every candidate is timed once (``iters`` calls +
    device sync), in round-robin order; the reported per-candidate time
    is the median across windows — the BENCH_NOTES r5 methodology, which
    makes a transient host slowdown a shared outlier window instead of a
    bias against one candidate.

    ``clock`` (default ``time.perf_counter``) and ``sync`` (default
    ``jax.block_until_ready``) are injectable so tests run the full
    search deterministically with a fake timer.

    Returns ``(best_config, table)``: the table holds one row per
    candidate — ``{"config", "median_s", "window_s"}`` — sorted
    fastest-first; ``best_config`` is the fastest candidate's config
    (``None`` when ``candidates`` is empty).
    """
    import time as _time

    clock = clock or _time.perf_counter
    sync = sync or _default_sync
    runners = []
    for cand in candidates:
        try:
            fn = build(cand)
            for _ in range(max(warmup, 0)):
                sync(fn())  # compile + steady-state entry, untimed
        except Exception as e:
            # one candidate failing to compile/run (Mosaic rejection,
            # VMEM overflow on an aggressive tile) must not abort the
            # whole search — skip it, keep measuring the rest
            tune_error_counter().inc()
            warnings.warn(
                f"autotune: candidate {cand} failed to build/run and "
                f"was skipped ({type(e).__name__}: {e})",
                RuntimeWarning, stacklevel=2,
            )
            continue
        runners.append((cand, fn))
    times = [[] for _ in runners]
    for _ in range(windows):
        for slot, (_, fn) in enumerate(runners):
            t0 = clock()
            out = None
            for _ in range(iters):
                out = fn()
            sync(out)
            times[slot].append(clock() - t0)
    table = []
    for (cand, _), ts in zip(runners, times):
        med = sorted(ts)[len(ts) // 2]
        table.append({"config": dict(cand),
                      "median_s": med / max(iters, 1),
                      "window_s": [round(t, 6) for t in ts]})
    table.sort(key=lambda r: r["median_s"])
    if not table:
        return None, []
    return dict(table[0]["config"]), table


# The cache-or-measure driver lives in tools/kernel_tune.py
# (``tune_shape``): it owns the composed-baseline interleaving and the
# fused-vs-composed verdict (entries carry ``fused_beats_composed``;
# flash's selection keeps composed where the tuner measured the
# kernel slower), and this module stays the mechanism layer
# (search + cache + metrics) with exactly one home for each piece.
