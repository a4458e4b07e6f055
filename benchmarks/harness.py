"""What every job shares: progress lines, the compile counter, the
device record and the profiler window."""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time


def note(msg):
    """Progress, on stderr: says how far a run got if the process dies."""
    print(f"benchmarks: {msg}", file=sys.stderr, flush=True)


def line(kind, **fields):
    """One earlier line of stdout (never the last)."""
    print(json.dumps({"line": kind, **fields}), flush=True)


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache traffic
    through ``jax.monitoring``; ``compiles`` read at both ends of the
    window says whether anything compiled inside it."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = self.cache_misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _secs(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def device_record(devices):
    """``device`` of the last line, as JAX reports the chips in use."""
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class TraceWindow:
    """``jax.profiler`` around a few seconds of the steady window; the
    ``.xplane.pb`` lands in a fixed directory inside the checkout."""

    def __init__(self, root, name):
        self.dir = os.path.join(root, ".bench_trace", name)
        self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def active(self):
        return self.t0 is not None and self.t1 is None

    @property
    def window_s(self):
        return self.t1 - self.t0

    def xplane(self):
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return max(found, key=os.path.getmtime) if found else None
