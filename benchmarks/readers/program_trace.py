"""Reader ``program_trace``: what the PROGRAM wrote into the profiler
trace, read beside the device planes of the same ``.xplane.pb``.

The program writes two things (``paddle_tpu``, PR 26):

- phase spans of its host loops, ``profiler.RecordEvent`` with fixed
  names (``serving::step``, ``serving::emit``, ``frontend::lock_wait``
  ...). They are events on the calling thread's line of the plane
  ``/host:CPU``, on the device planes' clock;
- ``jax.named_scope`` names on the operations of its compiled programs
  (``attn_core``, ``lm_head``, ``loss``, ``optimizer`` and every
  layer's attribute name). The TPU's trace keeps an operation's scope
  path in the stat ``tf_op`` of its event METADATA
  (``jit(_decode_body)/model/1/self_attn/attn_core/broadcast_in_dim:``),
  which ``jax.profiler.ProfileData`` does not hand out, and the only
  ``xplane_pb2`` installed is TensorFlow's, which this process must not
  import beside the chip. The few fields needed are read with
  protobuf's own decoder as the unknown fields of an empty message.

``run.py`` gives a reader only ``obs``: the trace is the newest
``*.xplane.pb`` under ``<checkout>/.bench_trace/``, where
``harness.TraceWindow`` puts it. The reduction is kept in
``obs["program_trace"]`` for the run's other metrics, and printed once
as two earlier lines, ``idle_by_span`` and ``device_by_scope``.

Metric files use ``{"reader": "program_trace", "quantity": ...}``:

- ``idle_attributed``: of the device's idle time between its first and
  last operation in the trace, the percentage that lies inside a phase
  span of the driver thread, the host line that holds
  ``serving::step`` events. Each idle interval goes to the INNERMOST
  span covering it; what no span covers is ``unattributed``. The own
  time of ``serving::step``, which covers the whole iteration, is no
  phase: it does not count, and the line ``idle_by_span`` gives it
  apart as ``serving::step (own)``.
- ``scope_ms``: device milliseconds a run of the program(s) whose
  module name contains ``program``, in operations whose scope path has
  one of ``scopes`` as a whole component (``transpose(jvp(loss))``
  counts as ``loss``; an operation merely NAMED like a scope does
  not). The union of their intervals, so nested events count once;
  the mean over the runs and over the chips.

A program without such spans or scopes (the parent of PR 26) gives
None for every quantity, and nothing here raises for it. The same is
read where the executables came from a compile cache that a commit
without the scopes wrote (the cache's key leaves metadata out): the
line ``device_by_scope`` then says that no operation had a path.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from google.protobuf import empty_pb2, unknown_fields

from benchmarks import harness
from benchmarks.readers import device_trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PHASE = re.compile(r"^(serving|frontend)::")
# the driver thread is the host line that holds these; the span covers
# a whole iteration, so its own time is no phase
STEP = "serving::step"
STEP_OWN = "serving::step (own)"
UNATTRIBUTED, NO_SCOPE = "unattributed", "(no scope)"
NOT_A_PHASE = (UNATTRIBUTED, STEP_OWN)


# --------------------------------------- messages without their schema
def _fields(buf):
    """``(field number, value)`` of one serialized message, decoded by
    protobuf as the unknown fields of an empty one: an int for a varint
    or fixed-width field, bytes for a length-delimited one."""
    msg = empty_pb2.Empty()
    msg.ParseFromString(bytes(buf))
    return [(f.field_number, f.data)
            for f in unknown_fields.UnknownFieldSet(msg)]


def _text(buf):
    return bytes(buf).decode("utf-8", "replace")


def op_scopes(xspace):
    """``{plane name: {operation's event name: scope path}}`` of the
    device planes of a serialized ``XSpace``. Field numbers are those
    of ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        parts = _fields(plane)
        name = next((_text(v) for n, v in parts if n == 2), "")
        if not device_trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for n, entry in parts:
            if n == 5:
                e = dict(_fields(entry))
                stat_names[e.get(1, 0)] = _text(
                    dict(_fields(e.get(2, b""))).get(2, b""))
        scopes = {}
        for n, entry in parts:
            if n != 4:
                continue
            meta = _fields(dict(_fields(entry)).get(2, b""))
            for m, stat in meta:
                if m != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) != "tf_op":
                    continue
                path = (_text(st[5]) if 5 in st
                        else stat_names.get(st.get(7), ""))
                op = next((_text(v) for k, v in meta if k == 2), "")
                scopes[op] = path.rstrip(":")
        out[name] = scopes
    return out


# --------------------------------------------------------- scope paths
def components(path):
    """Scope names of an operation's path, outermost first: nested jit
    names and the primitive at the end dropped, the wrappers of
    transforms taken off (``transpose(jvp(model))`` -> ``model``)."""
    out = []
    for comp in path.split("/")[:-1]:
        if comp.startswith(("jit(", "pjit(")):
            continue
        out.append(re.sub(r"^(?:\w+\()+|\)+$", "", comp))
    return out


def _group(path):
    """The key of the ``device_by_scope`` line: the path without layer
    indices, at most three names deep."""
    names = [c for c in components(path) if not c.isdigit()]
    return "/".join(names[:3]) or NO_SCOPE


# -------------------------------------------------------- host threads
def self_segments(spans):
    """Properly nested ``(start, end, name)`` spans of one thread ->
    disjoint segments in time order, each named by the innermost span
    that covers it."""
    segs, stack, cur = [], [], None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(start)
        if stack and start > cur:
            segs.append((cur, start, stack[-1][1]))
        cur = start if not stack else max(cur, start)
        stack.append((end, name))
    close_until(float("inf"))
    return segs


def attribute(idle, segs):
    """Seconds of the ``idle`` intervals by the name of the segment
    each part lies in; both lists in time order, times in ns."""
    out, j = {}, 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k, t = j, a
        while t < b:
            if k < len(segs) and segs[k][0] <= t:
                end, name = min(b, segs[k][1]), segs[k][2]
                k += 1
            else:
                end = min(b, segs[k][0]) if k < len(segs) else b
                name = UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (end - t) * 1e-9
            t = end
    return out


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


# ------------------------------------------------------- the reduction
def reduce_trace(planes, scopes):
    """``planes`` as ``device_trace.planes_of`` gives them, ``scopes``
    as ``op_scopes``. Returns, for each chip, the idle time by span and
    every program's operations with their scope paths; None without a
    device plane."""
    spans = []
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for _, events in lines:
            if any(n == STEP for n, _, _ in events):
                spans = [(s, s + d, n) for n, s, d in events
                         if PHASE.match(n)]
    # the step's span covers the whole iteration: its own time is named
    # apart, and is no phase
    segs = [(a, b, STEP_OWN if n == STEP else n)
            for a, b, n in self_segments(spans)]
    chips = []
    for pname, lines in planes:
        lines = dict(lines)
        if not device_trace.DEVICE_PLANE.match(pname) \
                or not lines.get(device_trace.OPS_LINE):
            continue
        ops = sorted((s, s + d, n) for n, s, d in
                     lines[device_trace.OPS_LINE])
        busy = _merged((s, e) for s, e, _ in ops)
        idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        starts = [s for s, _, _ in ops]
        tf_op = scopes.get(pname, {})
        programs = {}
        for n, s, d in lines.get(device_trace.MODULES_LINE, ()):
            rec = programs.setdefault(device_trace._module_name(n),
                                      {"runs": 0, "run_s": 0.0, "ops": []})
            rec["runs"] += 1
            rec["run_s"] += d * 1e-9
            lo, hi = (bisect.bisect_left(starts, t) for t in (s, s + d))
            rec["ops"] += [(a, b, tf_op.get(name, ""))
                           for a, b, name in ops[lo:hi]]
        chips.append({"idle_s": sum(b - a for a, b in idle) * 1e-9,
                      "by_span": attribute(idle, segs) if segs else {},
                      "programs": programs})
    return {"chips": chips} if chips else None


def seconds_by(rec, key):
    """``{key(path): device seconds a run}`` of one program's
    operations, each key's intervals united (nested events count
    once); operations whose key is None are left out."""
    keys, iv = {}, {}
    for a, b, path in rec["ops"]:
        if path not in keys:
            keys[path] = key(path)
        if keys[path] is not None:
            iv.setdefault(keys[path], []).append((a, b))
    return {k: sum(e - s for s, e in _merged(v)) * 1e-9 / rec["runs"]
            for k, v in iv.items()}


def _mean(values):
    values = list(values)
    return sum(values) / len(values)


def _lines(tr):
    """The two earlier lines, the chips' means."""
    chips = tr["chips"]
    names = sorted({n for c in chips for n in c["by_span"]})
    if names:
        harness.line(
            "idle_by_span", idle_s=_mean(c["idle_s"] for c in chips),
            spans={n: _mean(c["by_span"].get(n, 0.0) for c in chips)
                   for n in names})
    programs = {}
    for name in sorted({p for c in chips for p in c["programs"]}):
        recs = [c["programs"][name] for c in chips if name in c["programs"]]
        by = [seconds_by(r, _group) for r in recs]
        groups = {g for b in by for g in b}
        if groups <= {NO_SCOPE}:
            continue
        mean = {g: _mean(b.get(g, 0.0) for b in by) for g in groups}
        programs[name] = {
            "runs": _mean(r["runs"] for r in recs),
            "run_s": _mean(r["run_s"] / r["runs"] for r in recs),
            "scopes": dict(sorted(mean.items(), key=lambda kv: -kv[1])[:16])}
    if programs:
        harness.line("device_by_scope", programs=programs)
    elif any(c["programs"] for c in chips):
        harness.line(
            "device_by_scope", programs={},
            note="no operation of any program carries a scope path: the "
                 "program has no jax.named_scope, or its executables came "
                 "from a compile cache written by a commit without them "
                 "(the cache key leaves metadata out)")


def load(path):
    """The reduction of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    planes = device_trace.planes_of(ProfileData.from_serialized_xspace(data))
    return reduce_trace(planes, op_scopes(data))


def read(spec, obs):
    if not obs.get("trace"):        # this run left no device trace
        return None
    if "program_trace" not in obs:
        found = glob.glob(os.path.join(ROOT, ".bench_trace", "**",
                                       "*.xplane.pb"), recursive=True)
        obs["program_trace"] = tr = \
            load(max(found, key=os.path.getmtime)) if found else None
        if tr:
            _lines(tr)
    tr = obs["program_trace"]
    if not tr:
        return None
    q = spec["quantity"]
    if q == "idle_attributed":
        chips = [c for c in tr["chips"] if c["by_span"] and c["idle_s"]]
        if not chips:
            return None
        return _mean(100.0 * (1.0 - sum(c["by_span"].get(n, 0.0)
                                        for n in NOT_A_PHASE)
                              / c["idle_s"]) for c in chips)
    if q == "scope_ms":
        wanted = set(spec["scopes"])
        inside = lambda path: \
            True if wanted.intersection(components(path)) else None
        per_chip = []
        for c in tr["chips"]:
            recs = [r for n, r in c["programs"].items()
                    if spec["program"] in n]
            runs = sum(r["runs"] for r in recs)
            if runs:
                per_chip.append(sum(
                    seconds_by(r, inside).get(True, 0.0) * r["runs"]
                    for r in recs) / runs)
        if not any(per_chip):
            return None
        return 1e3 * _mean(per_chip)
    raise ValueError(f"program_trace: unknown quantity {q!r}")
