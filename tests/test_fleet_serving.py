"""paddle_tpu.serving.fleet — router + cross-process disaggregation.

The fleet contract, CPU-testable in one process: in-process replicas
are separate engines over separately-constructed-but-identical nets
(same seed), which is exactly the subprocess reality — the launch
entrypoint builds every replica from the same seed. The strong checks:

- token streams through the router are exact-equal to direct-to-engine
  and to ``net.generate``;
- a replica that dies mid-stream sheds with a terminal ``error`` +
  reason while UNSTARTED requests retry on another replica;
- the KV-transfer round trip (bf16 AND int8) adopts pages
  bit-identically to local prefill — arena equality, not just tokens;
- fleet saturation returns 429 with a reason BEFORE any stream opens.
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    FleetRouter,
    HTTPRejected,
    PagedServingEngine,
    PrefillWorker,
    RemotePrefillClient,
    ServingFrontend,
    TransferError,
    stream_generate,
)
from paddle_tpu.serving.fleet import kv_transfer

RNG = np.random.RandomState(13)


def build_net(seed=5):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
    )
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def net():
    return build_net()


def make_engine(net, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("page_size", 8)
    return PagedServingEngine(net, **kw)


def ref_tokens(net, ids, max_new):
    out = np.asarray(net.generate(
        Tensor(jnp.asarray(np.asarray(ids).reshape(1, -1))),
        max_new_tokens=max_new,
    ).numpy())
    return [int(t) for t in out[0][np.asarray(ids).size:]]


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


# ------------------------------------------------------------ wire frames
class _Buf:
    """Just enough socket to capture what send_frame writes."""

    def __init__(self):
        self.data = b""

    def sendall(self, b):
        self.data += b


def _frame_bytes(header, blob):
    buf = _Buf()
    kv_transfer.send_frame(buf, header, blob)
    return buf.data


def test_frame_roundtrip_and_crc():
    blob = bytes(range(256)) * 17
    a, b = socket.socketpair()
    try:
        kv_transfer.send_frame(a, {"kind": "x", "n": 3}, blob)
        hdr, got = kv_transfer.recv_frame(b)
        assert hdr == {"kind": "x", "n": 3} and got == blob
    finally:
        a.close()
        b.close()

    # corrupt one payload byte in flight -> CRC failure, not
    # silently-wrong pages
    raw = bytearray(_frame_bytes({"kind": "y"}, blob))
    raw[-1] ^= 0xFF
    c, d = socket.socketpair()
    try:
        c.sendall(bytes(raw))
        with pytest.raises(TransferError, match="CRC"):
            kv_transfer.recv_frame(d)
    finally:
        c.close()
        d.close()

    # truncated stream -> clean error, not a hang or a partial block
    e, f = socket.socketpair()
    try:
        e.sendall(_frame_bytes({"kind": "z"}, blob)[:200])
        e.close()
        with pytest.raises(TransferError):
            kv_transfer.recv_frame(f)
    finally:
        f.close()


def test_frame_bad_magic():
    a, b = socket.socketpair()
    try:
        a.sendall(b"NOPE" + b"\x00" * 12)
        with pytest.raises(TransferError, match="magic"):
            kv_transfer.recv_frame(b)
    finally:
        a.close()
        b.close()


# --------------------------------------------------- disaggregated prefill
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_remote_prefill_bit_identical_arena(net, cache_dtype):
    """The acceptance pin: after admitting the SAME request, the
    disaggregated engine's page arena is BIT-IDENTICAL to the local
    engine's — adoption equality, stronger than token equality."""
    worker = PrefillWorker(net, weights_version="wv1").start()
    try:
        client = RemotePrefillClient(
            "127.0.0.1", worker.port, expected_weights_version="wv1")
        local = make_engine(build_net(), cache_dtype=cache_dtype)
        disagg = make_engine(build_net(), cache_dtype=cache_dtype,
                             weights_version="wv1",
                             prefill_transport=client)
        ids = RNG.randint(0, 64, (1, 6))
        h_l = local.submit(ids, 4)
        h_d = disagg.submit(ids, 4)
        # one step admits (prefill + adopt) and decodes once
        local.step()
        disagg.step()
        assert disagg.remote_prefills == 1
        assert disagg.local_prefills == 0

        def leaves(flat):
            out = []
            for arr in flat:
                if hasattr(arr, "q"):
                    out += [arr.q, arr.scale]
                else:
                    out.append(arr)
            return out

        for al, ad in zip(leaves(local._flat), leaves(disagg._flat)):
            np.testing.assert_array_equal(np.asarray(al),
                                          np.asarray(ad))
        local.run_until_idle()
        disagg.run_until_idle()
        assert h_l.tokens == h_d.tokens
        if cache_dtype == "bfloat16":
            # bf16 path is also exact vs net.generate (int8 streams
            # are pinned against their own ratchet in test_serving)
            assert h_l.tokens == ref_tokens(net, ids, 4)
        assert local.page_pool.pages_in_use == 0
        assert disagg.page_pool.pages_in_use == 0
    finally:
        worker.stop()


def test_remote_prefill_streams_exact(net):
    """Full churn through the disaggregated engine: every stream
    exact-equal to net.generate, zero leaked pages, all prefills
    remote."""
    worker = PrefillWorker(net, weights_version="wv1").start()
    try:
        client = RemotePrefillClient(
            "127.0.0.1", worker.port, expected_weights_version="wv1")
        eng = make_engine(build_net(), weights_version="wv1",
                          prefill_transport=client)
        prompts = [RNG.randint(0, 64, (1, L)) for L in (6, 5, 9, 7)]
        max_news = [3, 8, 5, 6]
        handles = [eng.submit(p, m)
                   for p, m in zip(prompts, max_news)]
        eng.run_until_idle()
        for h, p, m in zip(handles, prompts, max_news):
            assert h.status == "DONE"
            assert h.tokens == ref_tokens(net, p, m)
        assert eng.remote_prefills == len(prompts)
        assert eng.local_prefills == 0
        assert eng.page_pool.pages_in_use == 0
        assert worker.served == len(prompts)
    finally:
        worker.stop()


def test_remote_prefill_fallback_when_down(net):
    """Transport down (nothing listening): the engine falls back to
    LOCAL prefill, streams stay exact, and the cooldown keeps the
    dead worker from being retried every admission."""
    client = RemotePrefillClient("127.0.0.1", free_port(),
                                 cooldown_s=60.0)
    eng = make_engine(build_net(), prefill_transport=client)
    prompts = [RNG.randint(0, 64, (1, 6)) for _ in range(3)]
    handles = [eng.submit(p, 4) for p in prompts]
    eng.run_until_idle()
    for h, p in zip(handles, prompts):
        assert h.status == "DONE"
        assert h.tokens == ref_tokens(net, p, 4)
    # first admission burned the connect, opened the cooldown; the
    # rest never touched the socket
    assert eng.remote_prefill_fallbacks == 1
    assert eng.local_prefills == 3
    assert not client.available()


def test_remote_prefill_weights_version_skew(net):
    """A worker serving DIFFERENT weights must never feed this engine:
    version skew is a TransferError -> local fallback, not silent
    wrong tokens."""
    worker = PrefillWorker(net, weights_version="STALE").start()
    try:
        client = RemotePrefillClient(
            "127.0.0.1", worker.port, cooldown_s=60.0,
            expected_weights_version="wv2")
        eng = make_engine(build_net(), weights_version="wv2",
                          prefill_transport=client)
        ids = RNG.randint(0, 64, (1, 6))
        h = eng.submit(ids, 4)
        eng.run_until_idle()
        assert h.status == "DONE"
        assert h.tokens == ref_tokens(net, ids, 4)
        assert eng.remote_prefills == 0
        assert eng.remote_prefill_fallbacks == 1
    finally:
        worker.stop()


# ----------------------------------------------------- replica status JSON
def test_healthz_status_fields(net):
    eng = make_engine(build_net(), weights_version="ckpt-42")
    fe = ServingFrontend(eng).start()
    try:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=10)
        conn.request("GET", "/healthz")
        st = json.loads(conn.getresponse().read())
        conn.close()
        assert st["accepting"] is True
        assert st["draining"] is False
        assert st["queue_depth"] == 0 and st["active"] == 0
        assert st["in_flight"] == 0
        assert st["free_pages"] == eng.page_pool.free_pages
        assert st["generation"] == 0
        assert st["weights_version"] == "ckpt-42"
        assert st["max_queue_size"] == eng.scheduler.max_queue_size
        assert st["page_pool"]["pages_in_use"] == 0
    finally:
        fe.stop()


def test_drain_endpoint_finishes_in_flight(net):
    """/drain stops admission (503 draining) but the in-flight stream
    runs to completion — the zero-dropped-requests rotation seam."""
    eng = make_engine(build_net())
    fe = ServingFrontend(eng).start()
    try:
        ids = [int(t) for t in RNG.randint(0, 64, (6,))]
        got = {}

        def long_stream():
            got["events"], _ = stream_generate(
                "127.0.0.1", fe.port,
                {"input_ids": ids, "max_new_tokens": 24},
            )

        th = threading.Thread(target=long_stream)
        th.start()
        # wait until the stream is actually running, then drain (or
        # until it finished — a hot engine can outrun the poll)
        deadline = time.monotonic() + 30
        while (eng.active_slots == 0 and "events" not in got
               and time.monotonic() < deadline):
            time.sleep(0.005)
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=10)
        conn.request("POST", "/drain")
        st = json.loads(conn.getresponse().read())
        conn.close()
        assert st["draining"] is True and st["accepting"] is False
        with pytest.raises(HTTPRejected) as ei:
            stream_generate("127.0.0.1", fe.port,
                            {"input_ids": ids, "max_new_tokens": 2})
        assert ei.value.code == 503
        assert ei.value.body["reason"] == "draining"
        th.join(timeout=120)
        ev = got["events"]
        assert ev[-1][0] == "done"
        toks = [d["token"] for e, d in ev if e == "token"]
        assert toks == ref_tokens(net, np.asarray(ids), 24)
        # undrain re-opens admission
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=10)
        conn.request("POST", "/undrain")
        st = json.loads(conn.getresponse().read())
        conn.close()
        assert st["accepting"] is True
        ev2, _ = stream_generate(
            "127.0.0.1", fe.port,
            {"input_ids": ids, "max_new_tokens": 2})
        assert ev2[-1][0] == "done"
    finally:
        fe.stop()


# ------------------------------------------------------------- the router
@pytest.fixture()
def two_replicas():
    fes = [ServingFrontend(make_engine(build_net())).start()
           for _ in range(2)]
    yield fes
    for fe in fes:
        fe.stop()


def test_router_streams_exact_and_spread(net, two_replicas):
    """Concurrent streams through the router: exact-equal to
    net.generate AND to direct-to-engine, and the least-loaded
    placement spreads them across both replicas."""
    fes = two_replicas
    router = FleetRouter([("127.0.0.1", fe.port) for fe in fes],
                         health_interval_s=0.05).start()
    try:
        prompts = [RNG.randint(0, 64, (1, L)) for L in (5, 7, 6, 9)]
        max_news = [4, 6, 5, 7]
        results = [None] * 4

        def one(i):
            results[i] = stream_generate(
                "127.0.0.1", router.port,
                {"input_ids": [int(t) for t in prompts[i][0]],
                 "max_new_tokens": max_news[i]})[0]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i in range(4):
            ev = results[i]
            assert ev is not None and ev[-1][0] == "done"
            toks = [d["token"] for e, d in ev if e == "token"]
            assert toks == ref_tokens(net, prompts[i], max_news[i])
        # direct-to-engine equality (replica 0, same weights)
        direct, _ = stream_generate(
            "127.0.0.1", fes[0].port,
            {"input_ids": [int(t) for t in prompts[0][0]],
             "max_new_tokens": max_news[0]})
        assert ([d["token"] for e, d in direct if e == "token"]
                == [d["token"] for e, d in results[0] if e == "token"])
        routed = router.metrics.requests.by_label()
        assert routed.get("0", 0) >= 1 and routed.get("1", 0) >= 1
        # per-replica health series made it to the exposition
        from paddle_tpu.observability import prometheus_text

        text = prometheus_text()
        assert "paddle_fleet_requests_total" in text
        assert "paddle_fleet_replica_free_pages" in text
    finally:
        router.stop()


def test_router_retries_unstarted_on_dead_replica(net, two_replicas):
    """A dead replica in the list: requests that land on it have not
    started, so they retry on the live one — every stream completes,
    the breaker opens, and placement stops picking the corpse."""
    live = two_replicas[0]
    router = FleetRouter(
        [("127.0.0.1", free_port()), ("127.0.0.1", live.port)],
        health_interval_s=30.0,  # no scrape rescue: the request path
        breaker_threshold=2, breaker_cooldown_s=60.0,
    )
    def resurrect_corpse():
        # make the dead replica look attractive (huge free_pages ->
        # lowest load score) so placement tries it FIRST every time
        r0 = router.replicas[0]
        r0.healthy = True
        r0.status = {"free_pages": 999, "queue_depth": 0, "active": 0}
        r0.status_time = router.clock()
        r0.breaker_open_until = 0.0

    router.start()  # its one synchronous scrape marks 0 unhealthy
    try:
        ids = [int(t) for t in RNG.randint(0, 64, (6,))]
        for _ in range(3):
            resurrect_corpse()
            ev, _ = stream_generate(
                "127.0.0.1", router.port,
                {"input_ids": ids, "max_new_tokens": 3})
            assert ev[-1][0] == "done"
            toks = [d["token"] for e, d in ev if e == "token"]
            assert toks == ref_tokens(net, np.asarray(ids), 3)
        assert router.metrics.retries.by_label().get(
            "conn_error", 0) >= 3
        # breaker opened at the threshold
        assert router.metrics.breaker_opens.by_label().get(
            "0", 0) >= 1
    finally:
        router.stop()


def test_router_midstream_death_sheds_with_reason(net):
    """A replica that dies AFTER streaming tokens: the client stream
    ends with a terminal error carrying reason=replica_failed (never
    replayed — tokens already left the building)."""
    # fake replica: SSE handshake + 2 tokens, then the socket dies
    import http.server

    class FakeReplica(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps({
                "accepting": True, "free_pages": 999,
                "queue_depth": 0, "active": 0,
            }).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            for i in range(2):
                self.wfile.write(
                    f"event: token\ndata: {{\"index\": {i}, "
                    f"\"token\": {i}}}\n\n".encode())
                self.wfile.flush()
            self.connection.close()  # mid-stream death

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                          FakeReplica)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    router = FleetRouter([("127.0.0.1", srv.server_address[1])],
                         health_interval_s=0.05).start()
    try:
        ev, _ = stream_generate(
            "127.0.0.1", router.port,
            {"input_ids": [1, 2, 3], "max_new_tokens": 8})
        assert [e for e, _ in ev] == ["token", "token", "error"]
        assert ev[-1][1]["reason"] == "replica_failed"
        # the router counts the abort after it has written the event
        # this client already holds: give its thread its turn
        deadline = time.monotonic() + 5.0
        while not router.metrics.stream_aborts.by_label() \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert router.metrics.stream_aborts.by_label().get(
            "replica_failed") == 1
    finally:
        router.stop()
        srv.shutdown()
        srv.server_close()


def test_router_saturation_429_before_stream(net):
    """Whole-fleet backpressure: every replica queue-full -> the
    router sheds HTTP 429 {"reason": "fleet_saturated"} BEFORE any
    SSE stream opens."""
    # deterministic saturation: 1-queue-slot engines whose step is
    # FROZEN (a no-op), so a queued request holds the queue full
    # forever — no race against the drain
    fes = []
    for _ in range(2):
        eng = make_engine(build_net(), max_batch_size=1,
                          max_queue_size=1)
        eng.step = lambda: time.sleep(0.005)
        fe = ServingFrontend(eng).start()
        h = eng.submit(RNG.randint(0, 64, (1, 6)), 4)
        assert h.status == "QUEUED"
        fes.append(fe)
    router = FleetRouter([("127.0.0.1", fe.port) for fe in fes],
                         health_interval_s=0.05).start()
    try:
        ids = [int(t) for t in RNG.randint(0, 64, (6,))]
        with pytest.raises(HTTPRejected) as ei:
            stream_generate("127.0.0.1", router.port,
                            {"input_ids": ids, "max_new_tokens": 2})
        assert ei.value.code == 429
        assert ei.value.body["reason"] == "fleet_saturated"
        assert ei.value.body["replicas_tried"] == 2
        assert router.metrics.shed.by_label().get(
            "fleet_saturated") == 1
        assert router.metrics.retries.by_label().get(
            "replica_busy") == 2
    finally:
        router.stop()
        for fe in fes:
            fe.stop()


def test_router_drain_rotates_replica_out(net, two_replicas):
    """POST /admin/drain/<i> stops placement on that replica while the
    other keeps serving; /admin/undrain restores it."""
    fes = two_replicas
    router = FleetRouter([("127.0.0.1", fe.port) for fe in fes],
                         health_interval_s=0.05).start()
    try:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=10)
        conn.request("POST", "/admin/drain/0")
        resp = json.loads(conn.getresponse().read())
        conn.close()
        assert resp["draining"] is True
        assert resp["replica_response"]["draining"] is True
        ids = [int(t) for t in RNG.randint(0, 64, (5,))]
        for _ in range(3):
            ev, _ = stream_generate(
                "127.0.0.1", router.port,
                {"input_ids": ids, "max_new_tokens": 2})
            assert ev[-1][0] == "done"
        routed = router.metrics.requests.by_label()
        assert routed.get("0", 0) == 0 and routed.get("1", 0) == 3
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=10)
        conn.request("POST", "/admin/undrain/0")
        assert json.loads(conn.getresponse().read())[
            "draining"] is False
        conn.close()
        # replica 0 accepts again (direct probe — placement may still
        # prefer the other one)
        ev, _ = stream_generate(
            "127.0.0.1", fes[0].port,
            {"input_ids": ids, "max_new_tokens": 2})
        assert ev[-1][0] == "done"
    finally:
        router.stop()


def test_router_watch_ckpt_root_auto_rotates(tmp_path):
    """``watch_ckpt_root=``: committing a NEW checkpoint triggers the
    existing rolling-reload walk with zero admin POSTs. Commits that
    predate router start are the baseline (no rotation); a torn
    in-flight ``.tmp`` save never triggers; after the new commit both
    replicas report the ckpt-step weights_version and serve its
    tokens."""
    from paddle_tpu.checkpoint import CheckpointManager

    def save_ckpt(net, step):
        mgr = CheckpointManager(str(tmp_path), network=net,
                                async_saves=False)
        mgr.save(step, blocking=True)
        mgr.close()

    save_ckpt(build_net(5), 1)  # pre-start baseline: must NOT rotate
    netB = build_net(9)
    refB = ref_tokens(netB, [2, 5], 4)
    engines = [make_engine(build_net(5)) for _ in range(2)]
    for e in engines:
        e.warmup()
    fes = [ServingFrontend(e).start() for e in engines]
    router = FleetRouter(
        [("127.0.0.1", fe.port) for fe in fes],
        health_interval_s=0.05, watch_ckpt_root=str(tmp_path),
        watch_interval_s=0.05,
    ).start()
    try:
        assert router._watched_step == 1
        time.sleep(0.3)
        assert router.last_watch_result is None  # baseline: no walk
        # an in-flight (never committed) save must not trigger either
        torn = tmp_path / "step_00000099.tmp"
        torn.mkdir()
        (torn / "w.p0.s0.npy").write_bytes(b"half")
        save_ckpt(netB, 9)  # the real publish
        deadline = time.monotonic() + 30
        while router._watched_step != 9:
            assert time.monotonic() < deadline, router.last_watch_result
            time.sleep(0.05)
        out = router.last_watch_result
        assert out["ok"] and out["step"] == 9
        assert [r["weights_version"] for r in out["results"]] == \
            ["ckpt-9", "ckpt-9"]
        # the fleet now serves the published weights, router-wide
        ev, _ = stream_generate(
            "127.0.0.1", router.port,
            {"input_ids": [2, 5], "max_new_tokens": 4})
        toks = [d["token"] for e, d in ev if e == "token"]
        done = [d for e, d in ev if e == "done"][0]
        assert toks == refB and done["weights_version"] == "ckpt-9"
    finally:
        router.stop()
        for fe in fes:
            fe.stop(close_engine=True)


def test_router_no_replicas_sheds_503():
    router = FleetRouter([("127.0.0.1", free_port())],
                         health_interval_s=30.0).start()
    try:
        with pytest.raises(HTTPRejected) as ei:
            stream_generate("127.0.0.1", router.port,
                            {"input_ids": [1, 2], "max_new_tokens": 2})
        assert ei.value.code == 503
        assert ei.value.body["reason"] == "no_replicas"
        assert router.metrics.shed.by_label().get(
            "no_replicas") == 1
    finally:
        router.stop()
