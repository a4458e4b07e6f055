"""The one general traffic generator: reads a mix's data file
(``benchmarks/traffic/<mix>.json``) and plans the run from ``--seed``.

Every seed gets the SAME multiset of lengths and arrival gaps, in
another order, with other token ids: lengths are the distribution's
quantiles at evenly spaced probabilities, gaps a fixed draw rescaled to
the stated rate. So runs differ by order and content, never by the
amount of work. Kinds of mix:

- ``train_batches``: ``batch`` x ``seq`` token ids a step.
- ``closed_loop``: ``clients`` callers, each sending its next request
  when the last one ends.
- ``open_loop``: arrivals on a schedule at ``rate_per_s`` whatever the
  server does (``arrivals.cv`` 1 is Poisson, larger is burstier), after
  ``ramp_s`` seconds of the same traffic that count as set-up.

Lengths: ``{"dist": "fixed"|"uniform"|"lognormal", ...}``; sharing:
``shared_prefix: {"tokens": n, "groups": g}`` makes the first ``n``
tokens of every prompt one of ``g`` seeded prefixes.
"""
from __future__ import annotations

import http.client
import json
import math
import queue
import statistics
import threading
import time

import numpy as np

MIX_SEED = 20240924   # the fixed draw behind every seed's gaps


def load_mix(path):
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("train_batches", "closed_loop", "open_loop"):
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    return mix


# ------------------------------------------------------------- lengths
def quantile_lengths(spec, n):
    """``n`` whole lengths: the distribution's quantiles at (i+.5)/n."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = spec["lo"] + (spec["hi"] - spec["lo"]) * u
    elif dist == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", None)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def describe(lengths):
    a = np.sort(np.asarray(lengths))
    return {"n": int(a.size), "min": int(a[0]),
            "median": float(np.median(a)),
            "p95": float(percentile(a.tolist(), 95)),
            "max": int(a[-1]), "mean": float(a.mean())}


def arrival_gaps(mix, n):
    """``n`` gaps whose sum is exactly ``n / rate``: one fixed gamma
    draw (shape 1/cv^2), the same for every seed."""
    cv = float(mix.get("arrivals", {}).get("cv", 1.0))
    g = np.random.default_rng(MIX_SEED).gamma(1.0 / cv ** 2, 1.0, n)
    return g * (n / float(mix["rate_per_s"])) / g.sum()


# --------------------------------------------------------------- plans
class Request:
    __slots__ = ("index", "due", "prompt", "max_new", "sent", "status",
                 "times", "tokens")

    def __init__(self, index, due, prompt, max_new):
        self.index, self.due = index, due
        self.prompt, self.max_new = prompt, int(max_new)
        self.sent = None          # perf_counter when the POST began
        self.status = "planned"   # -> sent -> DONE | rejected | error:...
        self.times = []           # perf_counter of each token at the client
        self.tokens = []


def _prompts(mix, rng, plens, vocab):
    share = mix.get("shared_prefix") or {}
    n_shared, groups = int(share.get("tokens", 0)), int(share.get("groups", 1))
    prefixes = [rng.integers(0, vocab, n_shared) for _ in range(groups)] \
        if n_shared else []
    out = []
    for i, n in enumerate(plens):
        ids = rng.integers(0, vocab, int(n))
        if n_shared:
            k = min(n_shared, int(n))
            ids[:k] = prefixes[i % groups][:k]
        out.append(ids.astype(np.int64))
    return out


def plan_requests(mix, seed, vocab, n):
    """``n`` requests: the mix's length quantiles, shuffled by seed."""
    rng = np.random.default_rng(seed)
    plens = quantile_lengths(mix["prompt_len"], n)
    olens = quantile_lengths(mix["output_len"], n)
    plens, olens = plens[rng.permutation(n)], olens[rng.permutation(n)]
    prompts = _prompts(mix, rng, plens, vocab)
    return [Request(i, None, p, o) for i, (p, o)
            in enumerate(zip(prompts, olens))], rng


def plan_open_loop(mix, seed, vocab, seconds):
    """Requests with due times (seconds from the generator's start)
    over ``ramp_s + seconds``; the window is the last ``seconds``."""
    total = float(mix.get("ramp_s", 0)) + float(seconds)
    n = max(1, int(round(float(mix["rate_per_s"]) * total)))
    reqs, rng = plan_requests(mix, seed, vocab, n)
    gaps = arrival_gaps(mix, n)[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


def plan_closed_loop(mix, seed, vocab):
    """Per client a list of requests. The first request of each client
    keeps only a share of its output, spread evenly over the clients,
    so the slots do not all turn over together."""
    c, k = int(mix["clients"]), int(mix.get("requests_per_client", 8))
    reqs, rng = plan_requests(mix, seed, vocab, c * k)
    per = [reqs[i * k:(i + 1) * k] for i in range(c)]
    if mix.get("stagger_first", True):
        for i, j in enumerate(rng.permutation(c)):
            first = per[i][0]
            first.max_new = max(1, int(first.max_new * (j + 1) / c))
    return per


def plan_train_batches(mix, seed, vocab):
    """A pool of ``pool`` fresh [batch, seq] token batches, made on the
    device in one jitted call."""
    import jax
    import jax.numpy as jnp

    shape = (int(mix.get("pool", 512)), int(mix["batch"]), int(mix["seq"]))
    return jax.jit(lambda k: jax.random.randint(
        k, shape, 0, vocab, jnp.int32))(jax.random.key(seed))


# -------------------------------------------------------------- client
def stream(port, req, timeout=600.0):
    """POST one request to ``/v1/generate`` and read its SSE stream,
    stamping every token with the client's clock. The benchmark's own
    copy of the reader: the program's is program code."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        req.sent = time.perf_counter()
        req.status = "sent"
        conn.request(
            "POST", "/v1/generate",
            body=json.dumps({"input_ids": req.prompt.tolist(),
                             "max_new_tokens": req.max_new}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            req.status = f"rejected:{resp.status}"
            resp.read()
            return req
        event = None
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if line.startswith("event:"):
                event = line[6:].strip()
            elif line.startswith("data:"):
                if event == "token":
                    req.times.append(time.perf_counter())
                    req.tokens.append(json.loads(line[5:])["token"])
                elif event == "done":
                    req.status = json.loads(line[5:]).get("status", "DONE")
                    return req
                elif event == "error":
                    d = json.loads(line[5:])
                    req.status = f"error:{d.get('reason') or d.get('status')}"
                    return req
        req.status = "error:stream_closed"
    except (OSError, http.client.HTTPException, ValueError) as e:
        req.status = f"error:{type(e).__name__}"
    finally:
        conn.close()
    return req


def _join(threads, timeout):
    """Join ``threads`` within ``timeout`` seconds; True if all ended."""
    end = time.perf_counter() + timeout
    for t in threads:
        t.join(max(0.0, end - time.perf_counter()))
    return not any(t.is_alive() for t in threads)


class ClosedLoop:
    """``clients`` threads, each walking its own list of requests."""

    def __init__(self, port, per_client):
        self.port, self.per_client = port, per_client
        self.stop = threading.Event()
        self.sent = []            # every request that was started
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._client, args=(reqs,),
                                         daemon=True, name=f"client-{i}")
                        for i, reqs in enumerate(per_client)]

    def _client(self, reqs):
        for r in reqs:
            if self.stop.is_set():
                return
            with self._lock:
                self.sent.append(r)
            stream(self.port, r)

    def start(self):
        for t in self.threads:
            t.start()
        return self

    def all_streaming(self):
        """True once every client has seen a token."""
        return all(any(r.times for r in reqs) for reqs in self.per_client)

    def requests(self):
        with self._lock:
            return list(self.sent)

    def join(self, timeout=30.0):
        return _join(self.threads, timeout)


class OpenLoop:
    """One dispatcher that releases each request when it is due to a
    pool of stream threads; never waits for the server."""

    def __init__(self, port, reqs, workers=128):
        self.port, self.reqs = port, reqs
        self.stop = threading.Event()
        self.t0 = None
        self._q = queue.SimpleQueue()
        self.threads = [threading.Thread(target=self._worker, daemon=True,
                                         name=f"stream-{i}")
                        for i in range(workers)]
        self.threads.append(threading.Thread(target=self._dispatch,
                                             daemon=True, name="dispatch"))

    def _dispatch(self):
        for r in self.reqs:
            while True:
                wait = self.t0 + r.due - time.perf_counter()
                if wait <= 0 or self.stop.is_set():
                    break
                time.sleep(min(wait, 0.05))
            if self.stop.is_set():
                break
            self._q.put(r)
        for _ in self.threads[:-1]:
            self._q.put(None)

    def _worker(self):
        while True:
            r = self._q.get()
            if r is None:
                return
            if not self.stop.is_set():
                stream(self.port, r)

    def start(self):
        self.t0 = time.perf_counter()
        for t in self.threads:
            t.start()
        return self

    def requests(self):
        return [r for r in self.reqs if r.sent is not None]

    def join(self, timeout=30.0):
        return _join(self.threads, timeout)


# ------------------------------------------------------------ measures
def percentile(values, p):
    """The p-th percentile (nearest rank) of a non-empty list."""
    a = sorted(values)
    return a[min(len(a) - 1, max(0, math.ceil(p / 100.0 * len(a)) - 1))]


def window_measures(reqs, w0, w1, t0=None):
    """What the clients saw between ``w0`` and ``w1`` (perf_counter):
    tokens received, gaps between a stream's consecutive tokens (by the
    later token), first-token delays from when each request was DUE
    (open loop: ``t0 + due``; closed loop: when it was sent), generator
    lateness, and the counts for ``attempted``/``failed``."""
    tokens, gaps, ttft, late = 0, [], [], []
    attempted = failed = in_flight = 0
    for r in reqs:
        due = r.sent if (t0 is None or r.due is None) else t0 + r.due
        if due is None:
            continue
        if w0 <= due < w1:
            attempted += 1
            if r.sent is not None and r.due is not None:
                late.append(r.sent - due)
            if r.status not in ("DONE", "sent", "planned"):
                failed += 1
            elif r.status != "DONE":    # streaming, or about to be sent
                in_flight += 1
        ts = r.times
        tokens += sum(1 for t in ts if w0 <= t < w1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1)
        if ts and w0 <= ts[0] < w1:
            ttft.append(ts[0] - due)
    return {"tokens": tokens, "gaps": gaps, "ttft": ttft, "late": late,
            "attempted": attempted, "failed": failed,
            "in_flight_at_cut": in_flight}


def backlog(reqs, at, t0=None):
    """``(waiting, in_system)`` at time ``at``: requests due by then
    that have no first token yet, and that have not ended yet."""
    waiting = in_system = 0
    for r in reqs:
        due = r.sent if (t0 is None or r.due is None) else t0 + r.due
        if due is None or due > at:
            continue
        ts = [t for t in r.times if t <= at]
        if not ts:
            waiting += 1
        if len(ts) < r.max_new and r.status in ("sent", "DONE", "planned"):
            in_system += 1
    return waiting, in_system


def resident_tokens(reqs, at):
    """Tokens whose keys and values the server holds at time ``at``:
    prompt + tokens so far, over the streams that have started and not
    ended by then (from the clients' own records)."""
    total = 0
    for r in reqs:
        ts = r.times
        if not ts or ts[0] > at:
            continue
        if len(ts) >= r.max_new and ts[-1] <= at:
            continue
        total += len(r.prompt) + sum(1 for t in ts if t <= at)
    return total
