"""Serving metrics: registry-based counters + histograms.

The serving quantities users actually page on — queue depth,
time-to-first-token, inter-token latency, slot occupancy, rejection and
timeout counts — live here as plain host-side counters/histograms (no
device work; observing a sample is a list append). Since the unified
telemetry PR these are thin subclasses of the process-wide
``paddle_tpu.observability`` instruments: every ServingMetrics
registers its set under ``paddle_serving_*`` names in the global
registry (replace-on-register — the newest engine's metrics own the
series), so one Prometheus scrape covers serving alongside training
and analysis telemetry. What the serving loop was DOING at a moment is
not here but in the ``profiler.RecordEvent`` phase spans of
``serving/engine.py`` and ``http_frontend.py``, which a jax profiler
trace holds on the device's clock; the histograms ``host_gap``,
``admit_hold``, ``read_wait``, ``prefill`` and ``submit_wait`` and the
counter ``steps_overlapped`` carry the same phases' totals over a whole
run, where a few seconds of trace hold too few requests.
"""
from __future__ import annotations

from ..observability import registry as _reg


class Counter(_reg.Counter):
    """Monotonic counter (optionally labeled by a reason string).

    The serving-side convenience shape over the registry Counter: one
    optional label dimension (``labelname``), ``by_label()`` readout."""

    def __init__(self, name, labelname="label", prom_name=None, help=""):
        super().__init__(name, help=help, prom_name=prom_name)
        self._labelname = labelname

    def inc(self, n=1, label=None, trace_id=None, **labels):
        """``label=`` is the serving shorthand for the configured
        labelname; registry-style ``**labels`` kwargs (what the
        inherited ``.labels()`` binding forwards) pass straight
        through, so both idioms work on the same instrument.
        ``trace_id`` records an exemplar on the bumped series."""
        if label is not None:
            labels[self._labelname] = label
        super().inc(n, trace_id=trace_id, **labels)

    def by_label(self):
        out = {}
        for k, v in self.series().items():
            d = dict(k)
            if self._labelname in d:
                out[d[self._labelname]] = \
                    out.get(d[self._labelname], 0) + v
        return out


class Histogram(_reg.Histogram):
    """Sample store with percentile readout.

    Memory-bounded for long-running servers: the window keeps the most
    recent ``maxlen`` samples (sliding-window percentiles — what a
    latency dashboard wants anyway), while ``count``/``sum``/Prometheus
    buckets stay exact running totals over ALL observations.
    ``snapshot()['mean']`` is the exact running ``sum/count``;
    p50/p90/p99/min/max describe only the window —
    ``snapshot()['window_count']`` tells dashboards how big that window
    population is (see the base class docstring for the full split)."""

    def __init__(self, name, unit="s", maxlen=65536, prom_name=None,
                 buckets=None, help=""):
        if buckets is None:
            buckets = (_reg.DEFAULT_BUCKETS if unit == "s"
                       else _reg.COUNT_BUCKETS)
        super().__init__(name, help=help, unit=unit, maxlen=maxlen,
                         buckets=buckets, prom_name=prom_name)


class ServingMetrics:
    """The engine's metric set. One instance per engine (or share one
    across engines to aggregate a process). Registered in the process
    registry under ``<namespace>_*`` with replace semantics: the most
    recently constructed instance owns the exported series."""

    def __init__(self, registry=None, namespace="paddle_serving"):
        ns = namespace
        self.submitted = Counter(
            "submitted", prom_name=f"{ns}_submitted_total",
            help="requests submitted")
        self.admitted = Counter(
            "admitted", prom_name=f"{ns}_admitted_total",
            help="requests admitted into the decode slab")
        self.completed = Counter(
            "completed", prom_name=f"{ns}_completed_total",
            help="requests finished DONE")
        self.rejected = Counter(          # labeled by reason
            "rejected", labelname="reason",
            prom_name=f"{ns}_rejected_total",
            help="requests rejected, by reason")
        self.timeouts = Counter(
            "timeouts", prom_name=f"{ns}_timeouts_total",
            help="requests expired past their deadline")
        self.sheds = Counter(             # labeled by reason
            "sheds", labelname="reason",
            prom_name=f"{ns}_sheds_total",
            help="in-flight requests shed by the engine, by reason "
                 "(pages_exhausted = a demand-grown decode page claim "
                 "that eviction could not satisfy)")
        self.tokens_out = Counter(
            "tokens_out", prom_name=f"{ns}_tokens_out_total",
            help="decode tokens emitted")
        self.prefill_tokens = Counter(
            "prefill_tokens", prom_name=f"{ns}_prefill_tokens_total",
            help="prompt tokens prefilled")
        self.guard_fires = Counter(       # labeled by fn key
            "guard_fires", labelname="fn",
            prom_name=f"{ns}_guard_fires_total",
            help="trace-guard recompile-storm fires seen by the engine")
        self.reloads = Counter(           # labeled by outcome
            "reloads", labelname="outcome",
            prom_name=f"{ns}_reloads_total",
            help="live weight reloads, by outcome (ok|verify_failed|"
                 "load_error|incompatible|error|...)")
        self.reload_ttft_spike = Histogram(
            "reload_ttft_spike",
            prom_name=f"{ns}_reload_ttft_spike_seconds",
            help="admission pause of one live reload (staged -> "
                 "applied): the worst-case extra TTFT a request queued "
                 "during the swap window saw")
        self.ttft = Histogram(            # submit -> first token
            "ttft", prom_name=f"{ns}_ttft_seconds",
            help="time to first token")
        self.itl = Histogram(             # inter-token latency
            "itl", prom_name=f"{ns}_itl_seconds",
            help="inter-token latency: per row and token, from the "
                 "return of the read that brought the row's token "
                 "before (its first token's time for the first decode "
                 "token) to the return of the read that brought this "
                 "one")
        self.e2e = Histogram(             # submit -> finished
            "e2e", prom_name=f"{ns}_e2e_seconds",
            help="end-to-end request latency")
        self.queue_wait = Histogram(      # submit -> admitted
            "queue_wait", prom_name=f"{ns}_queue_wait_seconds",
            help="queue wait before admission")
        self.queue_depth = Histogram(
            "queue_depth", unit="reqs",
            prom_name=f"{ns}_queue_depth",
            help="scheduler queue depth sampled per engine step")
        self.slot_occupancy = Histogram(
            "slot_occupancy", unit="slots",
            prom_name=f"{ns}_slot_occupancy",
            help="active decode-slab slots sampled per engine step")
        # the host loop's phases as window-wide totals; the same phases
        # are RecordEvent spans on the profiler trace's clock
        self.host_gap = Histogram(
            "host_gap", prom_name=f"{ns}_host_gap_seconds",
            help="driver thread, per decode step: return of the last "
                 "blocking read to the next launch of the decode "
                 "program: the host's work a step, which runs under "
                 "the program launched before that read (an admitted "
                 "row's first-token read is such a read; an idle "
                 "engine starts no sample, nor does a launch that no "
                 "read came before)")
        self.admit_hold = Histogram(
            "admit_hold", prom_name=f"{ns}_admit_hold_seconds",
            help="driver thread, per iteration that admitted a request "
                 "while other rows were resident: that iteration's "
                 "host_gap sample, the host's share of an admission "
                 "(pop, claims, uploads, the launches of prefill and "
                 "adopt), which runs under the step in flight: "
                 "nothing is read inside it")
        self.read_wait = Histogram(
            "read_wait", prom_name=f"{ns}_read_wait_seconds",
            help="driver thread, per decode step read: how long the "
                 "blocking read of a launched step's tokens waited; "
                 "above zero the host finished first and the device "
                 "never waited for it")
        self.steps_overlapped = Counter(
            "steps_overlapped",
            prom_name=f"{ns}_steps_overlapped_total",
            help="decode programs launched while the step before was "
                 "still unread (the host's work for it ran under a "
                 "program), the launch after an admission too; the "
                 "rest followed an idle engine or a speculative round")
        self.prefill = Histogram(
            "prefill", prom_name=f"{ns}_prefill_seconds",
            help="per admission: its start to its first token on the "
                 "host, which is read after the next decode launch: "
                 "gather, prefill, adopt and what was left of the "
                 "step in flight before them")
        self.submit_wait = Histogram(
            "submit_wait", prom_name=f"{ns}_submit_wait_seconds",
            help="per front-end request: received to engine.submit "
                 "returned (the wait for the driver's lock)")
        # what a decode step's cost depends on beside the batch: the
        # tokens it attends over, and (an expert model) how many
        # experts' weights it had to read
        self.resident_tokens = Histogram(
            "resident_tokens", unit="toks", buckets=_reg.TOKEN_BUCKETS,
            prom_name=f"{ns}_resident_tokens",
            help="per decode step: the rows' cache positions summed "
                 "(tokens the step attends over)")
        self.span_tokens = Histogram(
            "span_tokens", unit="toks", buckets=_reg.TOKEN_BUCKETS,
            prom_name=f"{ns}_span_tokens",
            help="per decode step: cache columns a row's attention read "
                 "was bounded to (the paged engine: the rung of "
                 "quantization.kv.span_ladder that holds the longest "
                 "row; a slab: max_seq_len); mean / max_seq_len is the "
                 "share of the table read")
        self.experts_touched = Histogram(
            "experts_touched", unit="experts",
            prom_name=f"{ns}_experts_touched",
            help="per decode step of an expert model: experts that got "
                 "at least one token, summed over the expert layers "
                 "(counted by the decode program, read with the next "
                 "tokens)")
        self.local_assignments = Histogram(
            "local_assignments", unit="assignments",
            prom_name=f"{ns}_local_assignments",
            help="per decode step of an expert model that holds a share "
                 "of its experts: (row, chosen expert) assignments that "
                 "landed on an expert held here, summed over the expert "
                 "layers (counted by the decode program)")
        self.dispatch_rows = Histogram(
            "dispatch_rows", unit="rows", buckets=_reg.TOKEN_BUCKETS,
            prom_name=f"{ns}_dispatch_rows",
            help="per decode step of an expert model that holds a share "
                 "of its experts: sorted rows the grouped matmuls were "
                 "handed, summed over the expert layers (the rung of "
                 "models.xing4.row_ladder that holds a layer's local "
                 "assignments; counted by the decode program); mean / "
                 "(expert layers x rows x top-k) is the share of rows "
                 "run")
        # what a net's decode program may count (``pop_step_counters``),
        # by the name it returns it under
        self.step_counters = {"experts_touched": self.experts_touched,
                              "local_assignments": self.local_assignments,
                              "dispatch_rows": self.dispatch_rows}
        # speculative decoding (serving.speculative): one round = one
        # draft proposal pass + one target verify launch
        self.spec_rounds = Counter(
            "speculative_rounds",
            prom_name=f"{ns}_speculative_rounds_total",
            help="speculative propose+verify rounds run")
        self.spec_proposed = Counter(
            "speculative_proposed_tokens",
            prom_name=f"{ns}_speculative_proposed_tokens_total",
            help="draft tokens proposed to the verifier")
        self.spec_accepted = Counter(
            "speculative_accepted_tokens",
            prom_name=f"{ns}_speculative_accepted_tokens_total",
            help="draft tokens the verifier accepted")
        self.spec_accept_length = Histogram(
            "speculative_accept_length", unit="toks",
            prom_name=f"{ns}_speculative_accept_length",
            help="tokens emitted per speculative round (accepted "
                 "prefix + the correction/bonus token; mean > 1 is "
                 "the whole win)")
        reg = registry
        if reg is None:
            from ..observability import get_registry

            reg = get_registry()
        reg.register_all([
            self.submitted, self.admitted, self.completed, self.rejected,
            self.timeouts, self.sheds, self.tokens_out,
            self.prefill_tokens,
            self.guard_fires, self.reloads, self.reload_ttft_spike,
            self.ttft, self.itl, self.e2e,
            self.queue_wait, self.queue_depth, self.slot_occupancy,
            self.host_gap, self.admit_hold, self.read_wait,
            self.steps_overlapped, self.prefill, self.submit_wait,
            self.resident_tokens, self.span_tokens, self.experts_touched,
            self.local_assignments, self.dispatch_rows,
            self.spec_rounds, self.spec_proposed, self.spec_accepted,
            self.spec_accept_length,
        ])
        # slo_class -> (ttft_child, itl_child, e2e_child). Lives on the
        # metrics OBJECT (not the engine) so the cache dies with the
        # instrument it binds to — serve_bench swaps engine.metrics
        # wholesale after warmup, and a cache held elsewhere would keep
        # observing into the discarded histograms.
        self._slo_children = {}

    def slo_children(self, slo_class):
        """Per-class bound children of the latency histograms, resolved
        once per class per metrics instance. Called at ADMISSION only;
        the returned bindings are what the hot loops observe into, so
        the per-token path never touches a label dict."""
        ch = self._slo_children.get(slo_class)
        if ch is None:
            ch = (
                self.ttft.labels(slo_class=slo_class),
                self.itl.labels(slo_class=slo_class),
                self.e2e.labels(slo_class=slo_class),
            )
            self._slo_children[slo_class] = ch
        return ch

    def observe_step(self, queue_depth, active_slots):
        self.queue_depth.observe(queue_depth)
        self.slot_occupancy.observe(active_slots)

    def report(self):
        """Plain-dict snapshot (what serve_bench prints as JSON)."""
        return {
            "counters": {
                "submitted": self.submitted.value,
                "admitted": self.admitted.value,
                "completed": self.completed.value,
                "rejected": self.rejected.value,
                "rejected_by_reason": self.rejected.by_label(),
                "timeouts": self.timeouts.value,
                "sheds": self.sheds.value,
                "sheds_by_reason": self.sheds.by_label(),
                "tokens_out": self.tokens_out.value,
                "prefill_tokens": self.prefill_tokens.value,
                "guard_fires": self.guard_fires.value,
                "guard_fires_by_fn": self.guard_fires.by_label(),
                "reloads": self.reloads.value,
                "reloads_by_outcome": self.reloads.by_label(),
                "speculative_rounds": self.spec_rounds.value,
                "speculative_proposed": self.spec_proposed.value,
                "speculative_accepted": self.spec_accepted.value,
                "steps_overlapped": self.steps_overlapped.value,
            },
            "speculative_accept_length":
                self.spec_accept_length.snapshot(),
            "reload_ttft_spike": self.reload_ttft_spike.snapshot(),
            "ttft": self.ttft.snapshot(),
            "itl": self.itl.snapshot(),
            "e2e": self.e2e.snapshot(),
            "queue_wait": self.queue_wait.snapshot(),
            "queue_depth": self.queue_depth.snapshot(),
            "slot_occupancy": self.slot_occupancy.snapshot(),
            "host_gap": self.host_gap.snapshot(),
            "admit_hold": self.admit_hold.snapshot(),
            "read_wait": self.read_wait.snapshot(),
            "prefill": self.prefill.snapshot(),
            "submit_wait": self.submit_wait.snapshot(),
            "resident_tokens": self.resident_tokens.snapshot(),
            "span_tokens": self.span_tokens.snapshot(),
            "experts_touched": self.experts_touched.snapshot(),
            "local_assignments": self.local_assignments.snapshot(),
            "dispatch_rows": self.dispatch_rows.snapshot(),
        }

    def observe_step_counters(self, counted):
        """One decode step's counters as the net's program returned
        them (``{name: small device array}``), each into the histogram
        of its name."""
        for name, value in counted.items():
            self.step_counters[name].observe(int(value))

    def render(self):
        """Human-readable table of the report."""
        r = self.report()
        lines = ["serving metrics", "-" * 15]
        for k, v in r["counters"].items():
            lines.append(f"{k:>20}: {v}")
        for name in ("ttft", "itl", "e2e", "queue_wait", "submit_wait",
                     "prefill", "host_gap", "admit_hold", "read_wait",
                     "queue_depth", "slot_occupancy"):
            s = r[name]
            if not s.get("count"):
                lines.append(f"{name:>20}: (no samples)")
                continue
            unit = s.get("unit", "s")
            scale = 1e3 if unit == "s" else 1.0
            u = "ms" if unit == "s" else unit
            lines.append(
                f"{name:>20}: n={s['count']} "
                f"p50={s['p50'] * scale:.3f}{u} "
                f"p90={s['p90'] * scale:.3f}{u} "
                f"p99={s['p99'] * scale:.3f}{u} "
                f"max={s['max'] * scale:.3f}{u}"
            )
        return "\n".join(lines)
