"""tpu_lint — run every static-analysis pass over the repo's own graphs.

Dogfood gate: builds a tiny-but-real Llama, traces the graphs that
matter in production — eval forward, the fused train step (forward +
backward + AdamW update), the serving engine's compiled decode-step,
and a standalone optimizer update — and lints each jaxpr; then runs the
AST pass over the whole source tree. Findings are diffed against the
checked-in baseline (``tools/tpu_lint_baseline.json``): exit 0 when no
new findings, 1 otherwise.

    python tools/tpu_lint.py                   # gate against baseline
    python tools/tpu_lint.py --json            # machine-readable report
    python tools/tpu_lint.py --update-baseline # accept current findings
                                               # (implies --concurrency)
    python tools/tpu_lint.py --audit-api       # also gate API surface
    python tools/tpu_lint.py --ast-only        # skip graph tracing (fast)
    python tools/tpu_lint.py --concurrency     # + collective/lock rules
    python tools/tpu_lint.py --memory          # + HBM footprint rules

``--concurrency`` adds the distributed-correctness passes: the
collective AST rules (rank-conditional-collective,
collective-off-main-thread) over the whole tree and the host
lock-discipline pass (lock-order-inversion, unlocked-shared-write,
blocking-call-under-lock) over the threaded runtimes. The jaxpr-level
collective-divergence rule always runs with the graph passes.
``--memory`` adds the donation-aware live-range HBM footprint pass
(hbm-budget-exceeded, peak-doubling, transient-blowup) over the same
graph inventory. ``make lint`` runs with ``--audit-api --concurrency
--memory``.

Runs on CPU (JAX_PLATFORMS=cpu is forced): tracing needs no chip, and
that is the point — hazards are caught before the graph ever reaches
one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=1"
    ).strip()

BASELINE_PATH = os.path.join(REPO, "tools", "tpu_lint_baseline.json")

# why each accepted finding is accepted — shown in the baseline file.
# Keys are Finding.key() strings (rule|graph|detail).
NOTES = {
    # ---- concurrency / collective passes (PR 15 dogfood) -------------
    "collective-off-main-thread|paddle_tpu/checkpoint/manager.py|"
    "thread:run->_write_and_commit:barrier":
        "preemption path only: register_preemption_handler's ckpt-"
        "preempt thread runs emergency_save. The REGULAR multiprocess "
        "save already forces blocking=True onto the calling thread "
        "(save() comment) — this reach is the SIGTERM emergency save, "
        "where every rank is preempting together and the train loop "
        "drains via wait() before the collectives run. Accepted; the "
        "lock sentinel + chaos smoke cover the runtime side.",
    "collective-off-main-thread|paddle_tpu/checkpoint/manager.py|"
    "thread:run->_write_and_commit:all_gather_object":
        "same preemption-path reach as the barrier entry above.",
    "collective-off-main-thread|paddle_tpu/checkpoint/manager.py|"
    "thread:run->_write_and_commit:broadcast_object_list":
        "same preemption-path reach as the barrier entry above.",
    "blocking-call-under-lock|paddle_tpu/serving/fleet/router.py|"
    "FleetRouter.reload_fleet:_reload_replica()->time.sleep":
        "by design: _reload_walk_lock exists ONLY to serialize rolling "
        "reload walks (a concurrent admin POST gets 409); nothing on "
        "the request path ever contends it, and the walk IS the slow "
        "drain-poll loop.",
    "unlocked-shared-write|paddle_tpu/serving/fleet/kv_transfer.py|"
    "PrefillWorker._fns:thread":
        "_program is only ever called from _handle_prefill's "
        "`with self._lock:` block — the write IS lock-protected, one "
        "call level above what the static pass tracks.",
    "unlocked-shared-write|paddle_tpu/serving/fleet/kv_transfer.py|"
    "PrefillWorker._blocks":
        "same as PrefillWorker._fns: _program runs under the caller's "
        "serving lock.",
}

# Fixes this linter's own findings forced (satellite: "document each
# applied fix in the lint baseline") — kept as history entries whose
# keys can never match a live finding.
FIXED = [
    {"key": "fixed|donation-miss|optimizer",
     "rule": "donation-miss",
     "why": "Adadelta/Adamax updates were eager per-op dispatches with "
            "no donation; now jitted update kernels with "
            "donate_argnums over param+state (optimizer/optimizer.py). "
            "RMSProp additionally donates mean_grad (arg 9)."},
    {"key": "fixed|donation-miss|jit.api.StaticFunction",
     "rule": "donation-miss",
     "why": "StaticFunction's layer path returns new_buffers while the "
            "input buffers die undonated — flagged, investigated, and "
            "REJECTED: Layer buffer arrays are aliased by external "
            "snapshots (ServingEngine._buffers, functional_state() "
            "holders), so donation would delete arrays a snapshot "
            "still references. Documented in jit/api.py _build; the "
            "finding stays accepted, not fixed."},
    # PR 15: fixes forced by the new concurrency passes' dogfood run
    {"key": "fixed|unlocked-shared-write|TraceGuard.findings",
     "rule": "unlocked-shared-write",
     "why": "TraceGuard._fire appended to findings outside the lock "
            "while reset() clears it under the lock; append moved "
            "under the lock (analysis/trace_guard.py)."},
    {"key": "fixed|unlocked-shared-write|AsyncSaver.last_error",
     "rule": "unlocked-shared-write",
     "why": "the writer thread published last_error unlocked while the "
            "train thread polls it; the write now takes the mailbox "
            "lock (checkpoint/async_saver.py)."},
    {"key": "fixed|unlocked-shared-write|FleetRouter.health-map",
     "rule": "unlocked-shared-write",
     "why": "placement scored replicas from UNLOCKED reads of r.status/"
            "r.in_flight while the scrape thread rewrites them under "
            "the lock (torn scores mixing two scrapes), and the ckpt-"
            "watch thread published _watched_step/last_watch_result "
            "unlocked; _eligible_snapshot now reads score inputs under "
            "the lock and the watcher publishes under it "
            "(serving/fleet/router.py)."},
    {"key": "fixed|unlocked-shared-write|TrainWatchdog.monitor",
     "rule": "unlocked-shared-write",
     "why": "the monitor thread wrote _peer_fired and last_dump_path "
            "unlocked while check()/tests read them from other "
            "threads; both now publish under the watchdog lock "
            "(training/resilience.py)."},
    {"key": "fixed|unlocked-shared-write|PrefillWorker.counters",
     "rule": "unlocked-shared-write",
     "why": "per-connection threads bumped served/errors with unlocked "
            "+= (lost updates under contention); increments moved "
            "under the serving lock (serving/fleet/kv_transfer.py)."},
]


def _tiny_net():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def graph_reports(config=None, verbose=False, memory=False,
                  mem_config=None, mem_tables=None):
    """Trace + lint the production graphs. Returns a Report.

    ``memory=True`` additionally runs the donation-aware live-range
    footprint pass (:mod:`paddle_tpu.analysis.memory_lint`) over every
    traced graph — same ratchet, new rules (hbm-budget-exceeded /
    peak-doubling / transient-blowup). ``mem_tables`` (a dict) is
    filled with each graph's estimate for ``--json`` output."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import analysis
    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.parallel import mesh as mesh_mod

    cfg = config or analysis.LintConfig(min_donation_bytes=32 << 10)
    mcfg = mem_config or analysis.MemoryConfig()
    if not mesh_mod.mesh_defined():
        mesh_mod.init_mesh()  # collective rule judges against real axes

    rep = analysis.Report()
    net = _tiny_net()
    params = {k: p.value for k, p in net.named_parameters()}
    buffers = {k: b.value for k, b in net.named_buffers()}
    ids = jnp.asarray(np.arange(16, dtype=np.int32).reshape(2, 8) % 128)

    def restore():
        net.load_functional_state(params, buffers)
        net.eval()

    def memlint(fn, *args, graph, donate_argnums=(), static_argnums=()):
        """The memory pass over one production graph (its own trace —
        the example args and donation mirror the lint_fn call)."""
        if not memory:
            return
        findings, est = analysis.lint_memory_fn(
            fn, *args, graph=graph, donate_argnums=donate_argnums,
            static_argnums=static_argnums, config=mcfg,
        )
        rep.extend(findings)
        if mem_tables is not None:
            mem_tables[graph] = est.to_dict()
        if verbose:
            print(f"  memory: {graph} peak "
                  f"{est.peak_bytes / (1 << 20):.2f} MiB "
                  f"(args {est.args_bytes / (1 << 20):.2f} MiB)",
                  flush=True)

    # ---- llama eval forward -------------------------------------------
    def fwd(params, buffers, ids):
        net.load_functional_state(params, buffers)
        net.eval()
        with tape.trace_scope(), tape.no_grad():
            out = net(Tensor(ids))
        return out.value

    if verbose:
        print("tracing llama_forward ...", flush=True)
    rep.extend(analysis.lint_fn(fwd, params, buffers, ids,
                                graph="llama_forward", config=cfg))
    restore()
    memlint(fwd, params, buffers, ids, graph="llama_forward")
    restore()

    # ---- fused train step: forward + backward + AdamW update ----------
    from paddle_tpu import optimizer as popt
    from paddle_tpu.jit.trainer import CompiledTrainStep
    from paddle_tpu.nn.layer.loss import CrossEntropyLoss

    opt = popt.AdamW(
        learning_rate=1e-3,
        parameters=[p for _, p in net.named_parameters()],
    )

    def loss_fn(logits, labels):
        return CrossEntropyLoss()(
            Tensor(logits.value.reshape(-1, logits.value.shape[-1])),
            Tensor(labels.value.reshape(-1)),
        )

    cts = CompiledTrainStep(net, loss_fn, opt)
    cts._build()
    opt_state = cts._gather_opt_state(params)
    labels = jnp.asarray(
        np.arange(16, dtype=np.int64).reshape(2, 8) % 128
    )
    if verbose:
        print("tracing llama_train_step (fwd+bwd+adamw) ...", flush=True)
    rep.extend(analysis.lint_fn(
        cts._step, params, opt_state, buffers, jnp.float32(1e-3),
        jnp.float32(1.0), jax.random.PRNGKey(0), (ids,), (labels,),
        graph="llama_train_step",
        donate_argnums=(0, 1, 2),  # what _finalize_jit donates
        config=cfg,
    ))
    restore()
    memlint(
        cts._step, params, opt_state, buffers, jnp.float32(1e-3),
        jnp.float32(1.0), jax.random.PRNGKey(0), (ids,), (labels,),
        graph="llama_train_step", donate_argnums=(0, 1, 2),
    )
    restore()

    # ---- serving compiled decode-step ---------------------------------
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(net, max_batch_size=2, max_seq_len=32,
                        min_bucket=8)
    B = eng.max_batch_size
    if verbose:
        print("tracing serving_decode_step ...", flush=True)
    rep.extend(analysis.lint_fn(
        eng._decode_body, eng._params, eng._buffers,
        jnp.zeros((B,), jnp.int32), eng._flat,
        jnp.zeros((B,), jnp.int32), jnp.float32(1.0),
        jax.random.PRNGKey(0),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
        graph="serving_decode_step",
        donate_argnums=(3,),  # the accelerator path donates the slab
        config=cfg,
    ))
    restore()
    memlint(
        eng._decode_body, eng._params, eng._buffers,
        jnp.zeros((B,), jnp.int32), eng._flat,
        jnp.zeros((B,), jnp.int32), jnp.float32(1.0),
        jax.random.PRNGKey(0),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
        graph="serving_decode_step", donate_argnums=(3,),
    )
    restore()
    eng.close()

    # ---- standalone optimizer step (the eager hot kernel) -------------
    from paddle_tpu.optimizer.optimizer import _adam_update

    p = jnp.ones((128, 128), jnp.float32)
    if verbose:
        print("tracing optimizer_step ...", flush=True)
    rep.extend(analysis.lint_fn(
        _adam_update.__wrapped__, p, p, p, p, jnp.float32(1e-3),
        jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-8),
        jnp.float32(1.0), jnp.float32(0.0), False,
        graph="optimizer_step",
        donate_argnums=(0, 1, 2),  # production _adam_update donation
        static_argnums=(10,),
        config=cfg,
    ))
    memlint(
        _adam_update.__wrapped__, p, p, p, p, jnp.float32(1e-3),
        jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-8),
        jnp.float32(1.0), jnp.float32(0.0), False,
        graph="optimizer_step", donate_argnums=(0, 1, 2),
        static_argnums=(10,),
    )

    # ---- leaked-tracer check over the dogfooded net -------------------
    rep.extend(analysis.lint_leaked_tracers(net, graph="llama_net"))
    return rep


def source_reports(concurrency=False):
    """Every source-level pass over the repo tree in ONE directory
    walk: the base AST lint always, plus (``--concurrency``) the
    collective and lock-discipline passes riding the same walk — each
    file is read AND parsed once no matter how many passes run."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.ast_lint import lint_tree

    passes = [analysis.ast_lint.lint_parsed]
    if concurrency:
        passes += [analysis.collective_lint.lint_parsed,
                   analysis.concurrency_lint.lint_parsed]
    rep = analysis.Report()
    for sub in ("paddle_tpu", "tools"):
        rep.extend(lint_tree(tuple(passes), os.path.join(REPO, sub),
                             root=REPO))
    return rep


def run_audit():
    """Satellite gate: API-surface drift shares this entrypoint."""
    from tools import api_audit

    rep = api_audit.collect()
    missing = sum(
        len(rep[k]["missing"])
        for k in ("top_level", "tensor_methods", "linalg", "nn_functional")
    )
    return rep, missing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept the current findings as the baseline")
    ap.add_argument("--audit-api", action="store_true",
                    help="also run tools/api_audit.py and gate on it")
    ap.add_argument("--ast-only", action="store_true",
                    help="skip graph tracing (source lint only)")
    ap.add_argument("--concurrency", action="store_true",
                    help="also run the collective + lock-discipline "
                         "passes (make lint's default)")
    ap.add_argument("--memory", action="store_true",
                    help="also run the donation-aware live-range HBM "
                         "footprint pass over every traced graph "
                         "(make lint's default)")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.update_baseline:
        # regenerating from a subset of passes would silently DROP the
        # skipped passes' accepted entries (and documented whys) from
        # the baseline, breaking the next full `make lint` — updating
        # requires the complete pass set
        if args.ast_only:
            ap.error("--update-baseline regenerates from ALL passes; "
                     "drop --ast-only")
        args.concurrency = True
        args.memory = True

    from paddle_tpu import analysis

    rep = analysis.Report()
    mem_tables = {}
    if not args.ast_only:
        rep.extend(graph_reports(verbose=args.verbose,
                                 memory=args.memory,
                                 mem_tables=mem_tables))
    rep.extend(source_reports(concurrency=args.concurrency))

    if args.update_baseline:
        _keys, old = analysis.load_baseline(args.baseline)
        old_notes = {e["key"]: e.get("why", "") for e in old
                     if not e.get("key", "").startswith("fixed|")}
        notes = dict(NOTES)
        for k, why in old_notes.items():
            notes.setdefault(k, why)
        entries = analysis.save_baseline(
            args.baseline, rep, notes=notes, extra_entries=FIXED
        )
        print(f"baseline written: {args.baseline} "
              f"({len(entries)} entries)")
        return 0

    keys, _entries = analysis.load_baseline(args.baseline)
    new, stale = analysis.diff_against_baseline(rep, keys)

    audit_missing = 0
    audit_rep = None
    if args.audit_api:
        audit_rep, audit_missing = run_audit()

    if args.json:
        out = {
            "findings": [f.to_dict() for f in rep.sorted()],
            "new": [f.to_dict() for f in new.sorted()],
            "stale_baseline_keys": stale,
            "counts": rep.counts(),
        }
        if mem_tables:
            out["memory"] = mem_tables
        if audit_rep is not None:
            out["api_audit"] = audit_rep
            out["api_audit_missing"] = audit_missing
        print(json.dumps(out, indent=1))
    else:
        for f in rep.sorted():
            mark = "NEW " if f.key() not in keys else "     "
            print(f"{mark}{f}")
        print(f"\n{len(rep)} finding(s) total, {len(new)} new, "
              f"{len(stale)} stale baseline entr(y/ies)")
        if stale and args.verbose:
            for k in stale:
                print(f"  stale: {k}")
        if audit_rep is not None:
            print(f"api audit: {audit_missing} unjustified missing names")

    if len(new):
        print(f"\nFAIL: {len(new)} finding(s) not in baseline "
              f"({os.path.relpath(args.baseline, REPO)}); fix, suppress "
              f"(# tpu-lint: disable=<rule>), or --update-baseline",
              file=sys.stderr)
        return 1
    if audit_missing:
        print("\nFAIL: api audit reports unjustified missing names",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
