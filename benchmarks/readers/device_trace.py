"""Reader ``device_trace``: reduces a profiler trace (``.xplane.pb``,
read with ``jax.profiler.ProfileData`` alone) to device busy time, the
time per run of a named program, and the breakdown the ledger keeps.

A TPU device is a plane ``/device:TPU:<n>``. Its line ``XLA Ops`` holds
one event per executed HLO operation and ``XLA Modules`` one per run of
a compiled program. Busy time is the UNION of the ``XLA Ops`` intervals
(nested and overlapping events count once); where a plane has no such
line every line but ``Steps`` and ``XLA Modules`` is taken.

Metric files use ``{"reader": "device_trace", "quantity": ...}``:

- ``idle_share``: 100 * (1 - busy / window), averaged over the chips.
- ``roofline_share``: 100 * (``work`` / ``peak``) / device seconds a
  run of the program(s) whose module name contains ``program``, where
  ``work`` names a number the job computed from shapes (``flops.py``)
  and ``peak`` a column of ``peaks.py``. It is the share of the bound
  ``peak`` names; the job says which bound binds.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SKIP = {"Steps", MODULES_LINE, "XLA TraceMe", "Framework Ops",
         "Framework Name Scope", "Source code"}


def _union(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals):
    """``(gap_seconds, index_of_next)`` between ``intervals`` given in
    start order, overlaps merged."""
    out, cur_e = [], None
    for i, (s, e) in enumerate(intervals):
        if cur_e is not None and s > cur_e:
            out.append((s - cur_e, i))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def _module_name(name):
    """``jit__decode_body(1234567)`` -> ``jit__decode_body``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name):
    """The trace names an op by its whole HLO line; keep the result's
    name and its shape: ``%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(
    ...)`` -> ``%fusion.1 f32[8,128]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    return f"{head} {re.split(r'[{ ]', rest.lstrip('('), maxsplit=1)[0]}"[:120]


def reduce_planes(planes):
    """``planes``: iterable of ``(name, [(line_name, [(event_name,
    start_ns, duration_ns), ...]), ...])``. Returns the reduced trace
    the metric reader and the breakdown use; times in seconds."""
    chips = []
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        lines = dict(lines)
        if OPS_LINE in lines:
            ops = list(lines[OPS_LINE])
        else:
            ops = [e for n, evs in lines.items() if n not in _SKIP
                   for e in evs]
        if not ops:
            continue
        iv = [(s * 1e-9, (s + d) * 1e-9) for _, s, d in ops]
        by_op = {}
        for n, _, d in ops:
            n = _op_name(n)
            by_op[n] = by_op.get(n, 0.0) + d * 1e-9
        mods = sorted(lines.get(MODULES_LINE, ()), key=lambda e: e[1])
        by_mod = {}
        for n, _, d in mods:
            rec = by_mod.setdefault(_module_name(n), [0, 0.0])
            rec[0] += 1
            rec[1] += d * 1e-9
        # idle gaps between program runs, by the programs on both sides
        by_gap = {}
        names = [_module_name(n) for n, _, _ in mods]
        for gap, nxt in _gaps([(s * 1e-9, (s + d) * 1e-9)
                               for _, s, d in mods]):
            label = f"{names[nxt - 1]}->{names[nxt]}"
            by_gap[label] = by_gap.get(label, 0.0) + gap
        chips.append({"plane": pname, "busy_s": _union(iv), "ops": by_op,
                      "modules": by_mod, "gaps": by_gap})
    return chips


def load(path):
    """Planes of an ``.xplane.pb`` in the shape ``reduce_planes`` takes."""
    from jax.profiler import ProfileData

    return planes_of(ProfileData.from_file(path))


def planes_of(profile):
    out = []
    for plane in profile.planes:
        lines = []
        for ln in plane.lines:
            lines.append((ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                    for ev in ln.events]))
        out.append((plane.name, lines))
    return out


def summarize(chips, window_s):
    """``busy_s`` averaged over the chips, and the breakdown."""
    if not chips:
        return None
    n = len(chips)
    ops, gaps, mods = {}, {}, {}
    for c in chips:
        for k, v in c["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / n
        for k, v in c["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / n
        for k, (cnt, sec) in c["modules"].items():
            rec = mods.setdefault(k, [0.0, 0.0])
            rec[0] += cnt / n
            rec[1] += sec / n
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(c["busy_s"] for c in chips) / n,
            "window_s": window_s, "chips": n, "modules": mods,
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)}}


def read(spec, obs):
    tr = obs.get("trace")
    if not tr:
        return None
    q = spec["quantity"]
    if q == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if q == "roofline_share":
        work = obs.get("work", {}).get(spec["work"])
        peak = obs["peaks"][spec["peak"]]
        runs = secs = 0.0
        for name, (cnt, sec) in tr["modules"].items():
            if spec["program"] in name:
                runs += cnt
                secs += sec
        if not work or not runs or not secs:
            return None
        # the work is all chips' together; seconds are one chip's mean
        return 100.0 * (work / (peak * tr["chips"])) / (secs / runs)
    raise ValueError(f"device_trace: unknown quantity {q!r}")
