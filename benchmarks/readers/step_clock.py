"""Reader ``step_clock``: the benchmark's own clock around runs of
train steps that end in a blocking read: ``obs["step_clock"]`` is a
list of ``(steps, seconds)``, each spanning well over 250 ms. Gives the
median span's milliseconds a step."""
from __future__ import annotations

import statistics


def read(spec, obs):
    spans = obs.get("step_clock")
    if not spans:
        return None
    return statistics.median(1e3 * s / n for n, s in spans)
