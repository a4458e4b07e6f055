"""Reader ``program_ms``: the mean device milliseconds of one run of the
programs whose name holds ``program``, from the traced window's
``modules`` (``{name: [runs, seconds]}``, which
``device_trace.summarize`` builds). A run that an edge of the window
cut counts as a run, with the seconds of it that the window holds."""
from __future__ import annotations


def read(spec, obs):
    modules = (obs.get("trace") or {}).get("modules") or {}
    runs = secs = 0.0
    for name, (cnt, sec) in modules.items():
        if spec["program"] in name:
            runs += cnt
            secs += sec
    return 1e3 * secs / runs if runs else None
