"""Reader ``scope_ops``: device time of a scope TOGETHER WITH the
operations that belong to it but carry no scope path, and that time's
share of a roofline.

``program_trace``'s ``scope_ms`` finds an operation by the
``jax.named_scope`` names in its path. The chip's compiler turns
``jax.lax.ragged_dot`` into a custom call whose metadata it writes
anew: in the trace its path is the bare name ``ragged-dot-none`` (and
``ragged-dot-metadata`` for the group offsets), whatever scope the
program gave it (seen in the program compiled for the chip and in the
first traced run, PR 28: 0.09 ms under ``moe_experts``, 10 ms of
grouped matmuls under no scope). Such an operation is named in the
metric's file (``ops``) and counted with the scope it was written
under. Metric files use::

    {"reader": "scope_ops", "quantity": "ms" | "roofline_share",
     "program": ..., "scopes": [...], "ops": [<bare path>, ...],
     "work": <key of obs["work"]>, "peak": <column of peaks.py>}

- ``ms``: device milliseconds a run of the program(s) whose module
  name contains ``program`` in operations under one of ``scopes`` or
  with one of ``ops`` as their whole path; the union of their
  intervals, the mean over runs and chips (as ``scope_ms``).
- ``roofline_share``: 100 * (``work`` / ``peak``) over those seconds.

Nothing to read (no trace, no such operation, no such work: the parent
of the PR that brought them) gives None.
"""
from __future__ import annotations

from benchmarks.readers import program_trace


def seconds(spec, obs):
    """Device seconds a run, or None."""
    # program_trace loads and keeps the reduction on its first read
    program_trace.read({"quantity": "scope_ms", "program": spec["program"],
                        "scopes": spec["scopes"]}, obs)
    tr = obs.get("program_trace")
    if not tr:
        return None
    scopes, bare = set(spec["scopes"]), set(spec.get("ops", ()))
    inside = lambda path: True if (
        path in bare or scopes.intersection(program_trace.components(path))
    ) else None
    per_chip = []
    for chip in tr["chips"]:
        recs = [r for n, r in chip["programs"].items()
                if spec["program"] in n]
        runs = sum(r["runs"] for r in recs)
        if runs:
            per_chip.append(sum(
                program_trace.seconds_by(r, inside).get(True, 0.0)
                * r["runs"] for r in recs) / runs)
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip)


def read(spec, obs):
    s = seconds(spec, obs)
    if s is None:
        return None
    if spec["quantity"] == "ms":
        return 1e3 * s
    if spec["quantity"] == "roofline_share":
        work = obs.get("work", {}).get(spec["work"])
        if not work:
            return None
        return 100.0 * (work / obs["peaks"][spec["peak"]]) / s
    raise ValueError(f"scope_ops: unknown quantity {spec['quantity']!r}")
