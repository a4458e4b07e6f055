"""paddle.profiler over the jax/XPlane profiler + host op tracer.

Reference parity: python/paddle/profiler/ + the host/CUPTI tracers and
summary machinery (paddle/fluid/platform/profiler/ — unverified, mount
empty). TPU redesign, three layers:

- **Device timelines**: the XLA/XPlane profiler (TensorBoard-viewable)
  captures real kernel times; ``RecordEvent`` spans map onto
  jax.profiler.TraceAnnotation so user regions appear in that trace.
- **Per-op host tracer**: while a Profiler is recording, every eager op
  dispatch is timed through a hook in core.dispatch (the analog of the
  reference auto-wrapping ops with RecordEvents) — no user code changes.
  Inside compiled steps individual ops are fused away by XLA; their cost
  lives in the device timeline, which is the correct attribution.
- **Summary tables + chrome trace**: ``Profiler.summary()`` prints
  sortable op/event tables (calls, total, avg, max, min, ratio) and
  ``export_chrome_tracing`` writes a chrome://tracing JSON of the host
  spans next to the XPlane dump.

The reference scheduler states are honored: ``make_scheduler(closed=,
ready=, record=, repeat=, skip_first=)`` drives ``Profiler.step()``
through CLOSED -> READY -> RECORD windows, invoking ``on_trace_ready``
at the end of every RECORD window.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"  # accepted for reference compat; maps to the accelerator
    TPU = "tpu"
    CUSTOM_DEVICE = "custom_device"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


_LOCK = threading.Lock()
_HOST_TIMES: dict = collections.defaultdict(list)
_OP_TIMES: dict = collections.defaultdict(list)
_EVENTS: list = []  # (name, kind, t_start, dur) for chrome export
# analysis/trace-guard event counts (name -> count): bounded by name
# cardinality, so counted even outside RECORD windows — a recompile
# storm must show in summary() whether or not a trace was open
_LINT_COUNTS: dict = collections.defaultdict(int)
_EPOCH = time.perf_counter()
# set while some Profiler is in a RECORD window; gates all appends so a
# bare RecordEvent in a profiler-less training loop cannot grow memory
_RECORDING = threading.Event()


def _record_op(name, dur):
    with _LOCK:
        _OP_TIMES[name].append(dur)
        _EVENTS.append((name, "op", time.perf_counter() - _EPOCH - dur, dur))


def reset_profiler_data():
    with _LOCK:
        _HOST_TIMES.clear()
        _OP_TIMES.clear()
        _EVENTS.clear()
        _LINT_COUNTS.clear()


def record_lint_event(name):
    """Count a static-analysis/trace-guard event (recompile storm,
    leaked tracer, ...). Counts always accumulate (bounded: keyed by
    name); when a RECORD window is open the event ALSO lands in the
    chrome trace as a zero-duration span, so recompile storms show up
    in traces instead of only as silent latency spikes. Each event also
    bumps the process metrics registry
    (``paddle_profiler_lint_events_total{event=...}``) so scrapes see
    lint activity without a profiler window open."""
    with _LOCK:
        _LINT_COUNTS[name] += 1
        if _RECORDING.is_set():
            _EVENTS.append((name, "lint", time.perf_counter() - _EPOCH,
                            0.0))
    try:
        from ..observability import get_registry

        get_registry().counter(
            "paddle_profiler_lint_events_total",
            help="static-analysis / trace-guard events, by event name",
        ).inc(event=name)
    except Exception:
        pass


def lint_event_counts():
    with _LOCK:
        return dict(_LINT_COUNTS)


def record_span(name, dur, kind="user"):
    """Inject an externally-timed span into the current RECORD window:
    it lands in the same tables as RecordEvent spans (the trace guard's
    recompile-storm marker comes this way). A no-op (returns False)
    outside a RECORD window, so nothing accumulates unbounded here."""
    if not _RECORDING.is_set():
        return False
    with _LOCK:
        _HOST_TIMES[name].append(dur)
        _EVENTS.append(
            (name, kind, time.perf_counter() - _EPOCH - dur, dur)
        )
    return True


class RecordEvent:
    """Context manager/decorator span (paddle.profiler.RecordEvent parity).

    The span is a ``jax.profiler.TraceAnnotation``: while a jax profiler
    trace is open it lands on the calling thread's line of the host
    plane, on the same clock as the device planes. Keyword arguments
    become the event's stats (``step=...``); keep what varies out of
    the name."""

    def __init__(self, name, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._ann = None
        self._t0 = None

    def begin(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name, **self._attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def end(self):
        if self._ann is not None:
            if _RECORDING.is_set():
                dur = time.perf_counter() - self._t0
                with _LOCK:
                    _HOST_TIMES[self.name].append(dur)
                    _EVENTS.append(
                        (self.name, "user",
                         self._t0 - _EPOCH, dur)
                    )
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Step-phase schedule (reference semantics): after ``skip_first``
    steps, cycle [closed | ready | record]; ``repeat=0`` = cycle
    forever."""
    cfg = {
        "closed": int(closed), "ready": int(ready), "record": int(record),
        "repeat": int(repeat), "skip_first": int(skip_first),
    }

    def schedule(step: int) -> int:
        s = step - cfg["skip_first"]
        if s < 0:
            return ProfilerState.CLOSED
        cycle = cfg["closed"] + cfg["ready"] + cfg["record"]
        if cycle == 0:
            return ProfilerState.RECORD
        if cfg["repeat"] and s >= cycle * cfg["repeat"]:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < cfg["closed"]:
            return ProfilerState.CLOSED
        if pos < cfg["closed"] + cfg["ready"]:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    schedule._config = cfg
    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready handler writing a chrome://tracing JSON of the
    recorded host spans (XPlane device dumps land in the same dir)."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        window = getattr(prof, "_window", 0)
        name = (worker_name or f"host_{os.getpid()}") + f".w{window}"
        events = []
        with _LOCK:
            snapshot = list(_EVENTS)
        for ev_name, kind, t0, dur in snapshot:
            events.append({
                "name": ev_name, "cat": kind, "ph": "X",
                "ts": t0 * 1e6, "dur": dur * 1e6,
                "pid": os.getpid(), "tid": 0 if kind == "user" else 1,
            })
        path = os.path.join(dir_name, f"{name}.chrome_trace.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        handler.last_path = path

    handler._export_dir = dir_name
    return handler


def _table_lines(title, data, sorted_by, unit):
    """Render {name: [durations_s]} as the calls/total/avg/max/min/ratio
    table both ``Profiler.summary()`` and ``ProfilerResult.summary()``
    print. ``unit`` is the seconds->display multiplier."""
    rows = []
    grand = sum(sum(v) for v in data.values()) or 1e-12
    for name, times in data.items():
        tot = sum(times)
        rows.append((
            name, len(times), tot * unit,
            tot / len(times) * unit, max(times) * unit,
            min(times) * unit, 100.0 * tot / grand,
        ))
    key = {"total": 2, "calls": 1, "avg": 3, "max": 4,
           "min": 5}.get(
        sorted_by if isinstance(sorted_by, str) else "total", 2
    )
    rows.sort(key=lambda r: r[key], reverse=(key != 5))
    w = max([len(r[0]) for r in rows] + [len("name")])
    head = (
        f"{'name':<{w}}  {'calls':>6}  {'total':>10}  "
        f"{'avg':>9}  {'max':>9}  {'min':>9}  {'ratio':>6}"
    )
    lines = [title, "-" * len(head), head, "-" * len(head)]
    for r in rows:
        lines.append(
            f"{r[0]:<{w}}  {r[1]:>6}  {r[2]:>10.3f}  {r[3]:>9.3f}"
            f"  {r[4]:>9.3f}  {r[5]:>9.3f}  {r[6]:>5.1f}%"
        )
    return lines


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = targets
        if isinstance(scheduler, dict):
            scheduler = make_scheduler(**scheduler)
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler  # reference (start, end) step-range form
            scheduler = make_scheduler(
                closed=0, ready=0, record=hi - lo, skip_first=lo, repeat=1
            )
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._export_dir = None
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._t0 = None
        self._window = 0

    # ------------------------------------------------------------ tracing
    def _start_tracing(self):
        from ..core import dispatch

        reset_profiler_data()  # each RECORD window reports its own data
        self._window += 1
        _RECORDING.set()
        dispatch._PROFILER_HOOK[0] = _record_op
        if not self.timer_only:
            import jax

            handler_dir = getattr(self.on_trace_ready, "_export_dir", None)
            self._logdir = self._export_dir or handler_dir or "./profiler_log"
            os.makedirs(self._logdir, exist_ok=True)
            with contextlib.suppress(Exception):
                jax.profiler.start_trace(self._logdir)
        self._tracing = True

    def _stop_tracing(self, fire_handler=True):
        from ..core import dispatch

        dispatch._PROFILER_HOOK[0] = None
        _RECORDING.clear()
        if self._tracing and not self.timer_only:
            import jax

            with contextlib.suppress(Exception):
                jax.profiler.stop_trace()
        self._tracing = False
        if fire_handler and self.on_trace_ready is not None:
            self.on_trace_ready(self)

    # ------------------------------------------------------------- control
    def start(self):
        self._t0 = time.perf_counter()
        if self.scheduler is None:
            self._state = ProfilerState.RECORD
            self._start_tracing()
        else:
            self._apply_state(self.scheduler(self._step))
        return self

    def stop(self):
        if self._tracing:
            self._stop_tracing(fire_handler=True)
        self.elapsed = time.perf_counter() - (self._t0 or time.perf_counter())

    def step(self, num_samples=None):
        """Advance the scheduler one training step."""
        self._step += 1
        if self.scheduler is not None:
            self._apply_state(self.scheduler(self._step))

    def _apply_state(self, new):
        old = self._state
        self._state = new
        recording = new in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        )
        if old == ProfilerState.RECORD_AND_RETURN and self._tracing:
            # a RECORD window just completed — close it even if the next
            # window starts immediately (closed=0, ready=0 schedules)
            self._stop_tracing(fire_handler=True)
        if recording and not self._tracing:
            self._start_tracing()
        elif not recording and self._tracing:
            self._stop_tracing(fire_handler=True)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------- summary
    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)

        def table(title, data):
            return _table_lines(title, data, sorted_by, unit)

        out = []
        with _LOCK:
            host = dict(_HOST_TIMES)
            ops = dict(_OP_TIMES)
            lint = dict(_LINT_COUNTS)
        if lint:
            out.append("Static-analysis / trace-guard events")
            out.append("-" * 36)
            for name in sorted(lint):
                out.append(f"{name}  x{lint[name]}")
            out.append("")
        if host:
            out += table(f"UserEvent Summary ({time_unit})", host)
            out.append("")
        if op_detail and ops:
            out += table(f"Operator Summary — host dispatch ({time_unit})",
                         ops)
            out.append("")
            out.append(
                "(compiled-step internals are in the XPlane device trace; "
                "open the log dir in TensorBoard)"
            )
        s = "\n".join(out) if out else "no profiler data recorded"
        print(s)
        return s


class ProfilerResult:
    """Summarizable view of an exported chrome-trace JSON.

    Holds the host-span events ``export_chrome_tracing`` wrote (device
    XPlane dumps stay TensorBoard territory); offers the same
    calls/total/avg/max/min table shape as ``Profiler.summary()`` so a
    trace can be re-summarized offline long after the run."""

    def __init__(self, events, path=None):
        self.path = path
        # normalized: (name, cat, ts_seconds, dur_seconds)
        self.events = events

    def names(self):
        return sorted({e[0] for e in self.events})

    def categories(self):
        return sorted({e[1] for e in self.events})

    def durations(self, name):
        """All span durations (seconds) recorded under ``name``."""
        return [e[3] for e in self.events if e[0] == name]

    def counts(self):
        out = collections.Counter()
        for name, _cat, _ts, _dur in self.events:
            out[name] += 1
        return dict(out)

    def total(self, name):
        return sum(self.durations(name))

    def time_range(self):
        """(first span start, last span end) in seconds; None if empty."""
        if not self.events:
            return None
        starts = [e[2] for e in self.events]
        ends = [e[2] + e[3] for e in self.events]
        return min(starts), max(ends)

    def summary(self, sorted_by="total", time_unit="ms"):
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)
        by_name = collections.defaultdict(list)
        for name, _cat, _ts, dur in self.events:
            by_name[name].append(dur)
        if not by_name:
            return "no events in trace"
        return "\n".join(_table_lines(
            f"Loaded trace summary ({time_unit})", by_name, sorted_by,
            unit,
        ))

    def __len__(self):
        return len(self.events)


def load_profiler_result(path):
    """Read back a chrome-trace JSON written by
    ``export_chrome_tracing`` (or any ``{"traceEvents": [...]}``/bare
    event-list chrome trace) into a :class:`ProfilerResult`. Only
    complete-duration events (``"ph": "X"``) carry durations; other
    phases are skipped. Times are normalized to seconds."""
    with open(path) as f:
        data = json.load(f)
    raw = data.get("traceEvents", data) if isinstance(data, dict) \
        else data
    if not isinstance(raw, list):
        raise ValueError(
            f"{path}: not a chrome trace (expected a traceEvents list)"
        )
    events = []
    for e in raw:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        events.append((
            str(e.get("name", "")), str(e.get("cat", "")),
            float(e.get("ts", 0.0)) / 1e6,
            float(e.get("dur", 0.0)) / 1e6,
        ))
    return ProfilerResult(events, path=path)
