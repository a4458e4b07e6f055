"""Reader ``engine_ratio``: how much one quantity of
``engine.metrics.report()`` (the program's ``ServingMetrics``) grew
between the window's two ends, over how much another grew, times
``scale``. A quantity is ``{"counter": name}`` or ``{"histogram":
name, "field": "sum" | "count"}``. A name the report lacks has not
grown; None when the denominator did not grow."""
from __future__ import annotations


def _growth(quantity, pair):
    if "counter" in quantity:
        v0, v1 = (r["counters"].get(quantity["counter"], 0) for r in pair)
    else:
        v0, v1 = (r.get(quantity["histogram"], {}).get(quantity["field"], 0)
                  for r in pair)
    return v1 - v0


def read(spec, obs):
    pair = obs.get("engine_report")
    if not pair:
        return None
    over = _growth(spec["denominator"], pair)
    if over <= 0:
        return None
    return _growth(spec["numerator"], pair) / over \
        * float(spec.get("scale", 1.0))
