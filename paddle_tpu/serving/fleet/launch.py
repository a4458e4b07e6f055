"""Subprocess entrypoints + spawn helpers for fleet components.

``python -m paddle_tpu.serving.fleet.launch --role replica`` puts a
``PagedServingEngine`` behind a ``ServingFrontend`` on an ephemeral
port; ``--role prefill`` starts a :class:`~.kv_transfer.PrefillWorker`.
Either prints exactly one line::

    FLEET_READY role=<role> port=<port>

to stdout once it is serving, then runs until SIGTERM/SIGINT (replicas
stop the frontend and close the engine on the way out). The model is
built from ``paddle.seed(--seed)`` + the tiny-llama knobs, so every
process launched with the same arguments serves IDENTICAL weights —
which is what makes router fail-over and disaggregated prefill
token-exact across processes.

:func:`spawn` is the parent-side helper ``serve_bench --fleet``,
``make fleet-smoke`` and the tests share: launch, wait for the READY
line, keep draining the child's output into a bounded tail ring (so a
chatty child can never block on a full pipe), and hand back the port.

Neither ``main`` nor the spawn helpers choose a platform: a child runs
on whatever its inherited environment says (the CPU gates and the tests
export ``JAX_PLATFORMS=cpu``). A chip belongs to one process, so N
replica children on a one-chip host cannot all claim it — they fail at
start-up instead of quietly serving from the CPU.
"""
from __future__ import annotations

import argparse
import collections
import os
import signal
import subprocess
import sys
import threading
import time


def build_net(args):
    import paddle_tpu as paddle
    from ...models import LlamaConfig, LlamaForCausalLM

    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=2 * args.hidden,
        num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _warmup(engine, args):
    """Compile (or AOT-load) every fixed-shape program before the
    READY line, so the first real requests pay sockets, not XLA.

    ``engine.warmup`` builds the decode step plus prefill AND adopt
    per prompt bucket directly — the local-fallback prefill programs
    are warm even when a prefill transport is attached, so a worker
    outage never stalls decode behind a compile. With ``--aot-cache``
    the finished executables persist, and a relaunched replica loads
    them instead of compiling: READY with zero traces, zero new
    trace-guard entries at first traffic. One real request then runs
    end-to-end (transport detached — warmup traffic must not consume
    the prefill pool) as the serve-path sanity pass; with a transport
    attached, one request per bucket additionally runs THROUGH it, so
    the prefill worker's lazily-compiled per-bucket programs are warm
    too — its first real remote prefill must not stall every replica
    behind an XLA compile under the worker's serving lock."""
    import numpy as np

    stats = engine.warmup(aot_cache=args.aot_cache)
    print(f"FLEET_WARMUP programs={stats['programs']} "
          f"aot_hits={stats['aot_hits']} "
          f"aot_saves={stats['aot_saves']}", flush=True)
    transport = engine.prefill_transport
    engine.prefill_transport = None
    try:
        L = min(args.min_bucket, args.max_seq - 2)
        h = engine.submit(np.zeros((1, L), np.int32), 2)
        engine.run_until_idle()
        assert h.status == "DONE", (
            f"warmup request ended {h.status} ({h.reason})"
        )
    finally:
        engine.prefill_transport = transport
    if transport is not None:
        bucket = engine.pool.bucket_for(min(args.min_bucket,
                                            args.max_seq - 2))
        while bucket <= args.max_seq:
            L = min(bucket, args.max_seq - 2)
            h = engine.submit(np.zeros((1, L), np.int32), 2)
            engine.run_until_idle()
            assert h.status == "DONE", (
                f"remote warmup for bucket {bucket} ended "
                f"{h.status} ({h.reason})"
            )
            if bucket >= args.max_seq:
                break
            bucket *= 2
    engine.metrics = type(engine.metrics)()
    engine.remote_prefills = 0
    engine.local_prefills = 0
    engine.remote_prefill_fallbacks = 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--role", choices=("replica", "prefill", "router"),
                    default="replica")
    # router role
    ap.add_argument("--replicas", default=None,
                    metavar="HOST:PORT,HOST:PORT",
                    help="router: comma-separated replica frontends")
    ap.add_argument("--watch-ckpt-root", default=None, metavar="DIR",
                    help="router: poll this checkpoint root and run "
                         "the rolling /admin/reload walk whenever a "
                         "NEW manifest-committed step appears — "
                         "publishing a checkpoint needs zero admin "
                         "POSTs")
    ap.add_argument("--watch-interval", type=float, default=1.0,
                    help="router: checkpoint-root poll period, seconds")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    # model (must match across the fleet for exactness)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    # engine geometry
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--min-bucket", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--weights-version", default="v0")
    ap.add_argument("--prefill-worker", default=None, metavar="HOST:PORT",
                    help="attach this replica to a prefill pool worker "
                         "(disaggregated prefill with local fallback)")
    ap.add_argument("--aot-cache", default=None, metavar="DIR",
                    help="persistent AOT compile cache: warmup "
                         "serializes compiled programs here; a "
                         "relaunch loads them instead of compiling")
    ap.add_argument("--no-warmup", dest="warmup", action="store_false")
    args = ap.parse_args(argv)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *a: stop.set())

    # name this process's trace rows before any span exists — stitched
    # fleet traces show router/replica/prefill_worker as separate
    # Perfetto process lanes (replicas additionally keyed by port once
    # known, via PADDLE_TPU_TRACE_PROCESS set by the spawner)
    from ...observability.tracing import set_process_name

    set_process_name(os.environ.get("PADDLE_TPU_TRACE_PROCESS")
                     or ("prefill_worker" if args.role == "prefill"
                         else args.role))

    if args.role == "router":
        if not args.replicas:
            ap.error("--role router requires --replicas")
        from .router import FleetRouter

        router = FleetRouter(
            [s.strip() for s in args.replicas.split(",") if s.strip()],
            host=args.host, port=args.port,
            watch_ckpt_root=args.watch_ckpt_root,
            watch_interval_s=args.watch_interval,
        ).start()
        print(f"FLEET_READY role=router port={router.port}",
              flush=True)
        stop.wait()
        router.stop()
        return 0

    net = build_net(args)

    if args.role == "prefill":
        from .kv_transfer import PrefillWorker

        worker = PrefillWorker(
            net, host=args.host, port=args.port,
            weights_version=args.weights_version,
        ).start()
        print(f"FLEET_READY role=prefill port={worker.port}",
              flush=True)
        stop.wait()
        worker.stop()
        return 0

    from ..http_frontend import ServingFrontend
    from ..paged_engine import PagedServingEngine

    transport = None
    if args.prefill_worker:
        from .kv_transfer import RemotePrefillClient

        whost, _, wport = args.prefill_worker.rpartition(":")
        transport = RemotePrefillClient(
            whost or "127.0.0.1", int(wport),
            expected_weights_version=args.weights_version,
        )
    engine = PagedServingEngine(
        net, max_batch_size=args.max_batch, max_seq_len=args.max_seq,
        min_bucket=args.min_bucket, page_size=args.page_size,
        num_pages=args.num_pages, max_queue_size=args.max_queue,
        cache_dtype=args.cache_dtype,
        weights_version=args.weights_version,
        prefill_transport=transport,
    )
    if args.warmup:
        _warmup(engine, args)
    fe = ServingFrontend(engine, host=args.host,
                         port=args.port).start()
    print(f"FLEET_READY role=replica port={fe.port}", flush=True)
    stop.wait()
    fe.stop(close_engine=True)
    return 0


# --------------------------------------------------------------- spawning
class FleetProc:
    """A spawned fleet component: the Popen, its READY port, and a
    bounded tail of its merged stdout/stderr (diagnostics on failure —
    and the drain keeps the child from blocking on a full pipe).
    ``lines`` is the queue the spawn-time reader thread feeds (one
    reader per child; ``None`` marks EOF)."""

    def __init__(self, proc, port, role, lines):
        self.proc = proc
        self.port = port
        self.role = role
        self._lines = lines
        self.tail = collections.deque(maxlen=400)
        self._drainer = threading.Thread(target=self._drain,
                                         daemon=True)
        self._drainer.start()

    def _drain(self):
        while True:
            line = self._lines.get()
            if line is None:
                return
            self.tail.append(line.rstrip("\n"))

    @property
    def alive(self):
        return self.proc.poll() is None

    def terminate(self, timeout_s=15.0):
        """Graceful stop (SIGTERM -> SIGKILL after the timeout)."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5)
        return self.proc.returncode

    def kill(self):
        """SIGKILL — the fleet smoke's replica-death scenario."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(5)
        return self.proc.returncode


def _popen(role, cli_args, env):
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    child_env = dict(os.environ)
    child_env.update(env or {})
    child_env["PYTHONUNBUFFERED"] = "1"
    child_env["PYTHONPATH"] = (
        repo_root + os.pathsep + child_env.get("PYTHONPATH", "")
    )
    cmd = [sys.executable, "-m", "paddle_tpu.serving.fleet.launch",
           "--role", role, *[str(a) for a in cli_args]]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=child_env, cwd=repo_root,
    )
    # a reader thread owns the pipe: readline() in the caller would
    # block past the deadline on a child that wedges without printing
    import queue as _queue

    lines = _queue.Queue()

    def _reader():
        try:
            for line in proc.stdout:
                lines.put(line)
        except ValueError:
            pass  # pipe closed at shutdown
        lines.put(None)

    threading.Thread(target=_reader, daemon=True).start()
    return proc, lines


def _wait_ready(proc, lines, role, timeout_s):
    import queue as _queue

    head = []
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            line = lines.get(timeout=min(
                1.0, max(deadline - time.monotonic(), 0.05)))
        except _queue.Empty:
            continue
        if line is None:
            proc.wait()
            raise RuntimeError(
                f"fleet {role} exited rc={proc.returncode} before "
                f"READY:\n" + "\n".join(head[-40:])
            )
        head.append(line.rstrip("\n"))
        if line.startswith("FLEET_READY"):
            port = int(line.rsplit("port=", 1)[1].strip())
            return FleetProc(proc, port, role, lines)
    proc.kill()
    raise RuntimeError(
        f"fleet {role} not READY within {timeout_s}s:\n"
        + "\n".join(head[-40:])
    )


def spawn(role="replica", cli_args=(), *, timeout_s=300.0, env=None):
    """Launch one fleet component subprocess and wait for its READY
    line. Returns a :class:`FleetProc`. Raises RuntimeError (with the
    child's output) when the child dies or never reports ready."""
    proc, lines = _popen(role, cli_args, env)
    return _wait_ready(proc, lines, role, timeout_s)


def spawn_all(specs, *, timeout_s=300.0, env=None):
    """Launch MANY components concurrently: all Popens start first,
    then each READY line is awaited — the children's XLA warmups run
    in parallel instead of being serialized by the parent. ``specs``
    is a list of ``(role, cli_args)``. On any failure the already-
    spawned children are killed before the error propagates."""
    started = [(role, *_popen(role, args, env)) for role, args in specs]
    procs = []
    try:
        for role, proc, lines in started:
            procs.append(_wait_ready(proc, lines, role, timeout_s))
    except BaseException:
        for _, proc, _ in started:
            if proc.poll() is None:
                proc.kill()
        raise
    return procs


if __name__ == "__main__":
    from ...jit import place_compile_cache

    place_compile_cache()
    sys.exit(main())
