"""Reader ``generator``: the load generator's own records.
``quantity: "late_p95"`` is the 95th percentile of (sent - due) over
the open-loop requests due in the window, times ``scale``."""
from __future__ import annotations

from benchmarks.loadgen import percentile


def read(spec, obs):
    if spec["quantity"] != "late_p95":
        raise ValueError(f"generator: unknown quantity {spec['quantity']!r}")
    late = obs.get("generator_late")
    if not late:
        return None
    return percentile(late, 95) * float(spec.get("scale", 1.0))
