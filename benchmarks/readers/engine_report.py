"""Reader ``engine_report``: a histogram of ``engine.metrics.report()``
(the program's ``ServingMetrics``) between the window's two ends. The
histograms keep running ``sum`` and ``count`` exactly and percentiles
only over a sliding window of samples, so this reads the mean,
``(sum1 - sum0) / (count1 - count0)``, times ``scale``; with
``share_of`` the mean is given as a percentage of that engine size."""
from __future__ import annotations


def read(spec, obs):
    pair = obs.get("engine_report")
    if not pair:
        return None
    h0, h1 = (r.get(spec["histogram"], {}) for r in pair)
    n = h1.get("count", 0) - h0.get("count", 0)
    if n <= 0:
        return None
    mean = (h1.get("sum", 0.0) - h0.get("sum", 0.0)) / n
    if "share_of" in spec:
        return 100.0 * mean / float(obs["engine"][spec["share_of"]])
    return mean * float(spec.get("scale", 1.0))
