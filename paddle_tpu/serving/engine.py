"""Continuous-batching LLM serving engine.

The TPU-first serving shape (cf. the kernel-fusion serving stacks in
PAPERS.md): keep the device running ONE compiled fixed-shape decode-step
program over a resident KV slab, and do all request lifecycle work —
admission, retirement, deadlines, metrics — in a host-side loop that
runs UNDER the steps: the loop keeps at most one decode step in flight
(it launches step n+1 before it reads step n's tokens, see
``ServingEngine._decode_once``), so the device goes from one program to
the next while the host emits, takes the front end's lock and builds
the next inputs. An admission is two more launches of such an
iteration: its prefill is the new row's step in flight, and its first
token is read after the next decode launch, like any other
(``ServingEngine._seat``). Three compiled programs total:

- **prefill** (one per power-of-two prompt bucket): runs a right-padded
  prompt through the cache path and emits the first token. Bucketing
  bounds compile count at O(log S_max); padding is numerically exact
  because pad positions only ever write cache slots that decode
  overwrites before the mask exposes them.
- **adopt** (one per bucket): copies a prefill block into a free row of
  the decode slab (``dynamic_update_slice`` at a traced slot index — no
  per-slot recompiles) and the prefill's first token into the row's
  place of the tokens the next decode step is fed from the device.
- **decode step** (exactly one): ``[max_batch]`` tokens at per-row
  positions -> next tokens. Every row sits at its own depth — this is
  what the vector-``pos`` cache path in ``models.llama`` exists for.
  A continuing row's input token is the step before's output, taken
  on the device, and a new row's its prefill's; the host supplies it
  only where it alone has it (a remote prefill's, a speculative
  round's).
  Free rows ride along as masked garbage (their writes land on slots
  the next adoption overwrites), so admission and retirement NEVER
  trigger a recompile or stall in-flight sequences.

Token streams are exact-equal to ``net.generate`` (same cache dtype):
the per-row program computes the same attention over the same masked
cache, so continuous batching is a scheduling optimization, not an
accuracy trade. The tier-1 serving test pins this token-for-token.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiler
from ..models.generation import (
    DEFAULT_CACHE_DTYPE,
    _select_next,
    alloc_kv_caches,
    cache_layout,
    decode_step,
    keeps_kv_pairs,
    keeps_row_state,
    prefill,
    row_layout,
    token_arrays_are_kv_pairs,
    unflatten_caches,
)
from ..observability.tracing import get_tracer
from .kv_pool import KVCachePool
from .metrics import ServingMetrics
from .sampling_keys import SamplingKeySource
from .scheduler import (
    CANCELLED,
    DONE,
    REASON_ENGINE_CLOSED,
    REASON_SHAPE_MISMATCH,
    REASON_TIMEOUT,
    REASON_TOO_LONG,
    REJECTED,
    RUNNING,
    TIMEOUT,
    RejectedError,
    Request,
    RequestHandle,
    Scheduler,
)


def _flatten(caches):
    return [a for kv in caches for a in kv]


# per-layer tuples of a flat list of cache arrays, as many a layer as
# the config's cache statement names: _unflatten(flat, cfg)
_unflatten = unflatten_caches


def step_counters(net):
    """What ``net`` counted in the forward just traced: small arrays by
    name, which a decode program returns beside the next tokens
    (``ServingMetrics.observe_step_counters``) and a prefill program
    drops, so that no tracer outlives its trace on the net. Most nets
    count nothing, and the empty dict adds no output to their
    program."""
    pop = getattr(net, "pop_step_counters", None)
    return pop() if pop is not None else {}


# Why each option is refused over a net that keeps a state a ROW beside
# its pages (``generation.row_layout``), one sentence each
_ROW_STATE_REFUSALS = {
    "int8 cache storage":
        "it quantizes K and V per head and has no form for the state",
    "speculative decoding":
        "a rejected draft would have to roll the row's state back",
    "the prefix cache":
        "it would have to snapshot the state at page boundaries, and its "
        "chunked prefill of a tail would start from a state no page holds",
    "KV tiering": "it spills and restores the prefix cache's pages",
    "remote prefill": "its wire block carries pages and no row state",
}


def build_prefill_body(net, do_sample, top_k, top_p):
    """The (un-jitted) bucketed-prefill program body every prefill site
    shares: the engines' per-bucket programs and the fleet tier's
    remote :class:`~.fleet.kv_transfer.PrefillWorker` trace the SAME
    function, which is what makes a disaggregated prefill bit-identical
    to a local one (same weights -> same block, same first token).

    Over a net that keeps a state a row the block's row arrays come
    back holding the prompt's final state (``generation.prefill`` hands
    the net ``length``), and the program is called
    ``prefill_state_body`` where every other net's is ``prefill_body``:
    a trace reader can tell the two apart."""

    def prefill_body(params, buffers, ids, length, flat_block, temperature,
                     key):
        net.load_functional_state(params, buffers)
        net.eval()
        logits, caches = prefill(
            net, ids, _unflatten(flat_block, net.config), length=length
        )
        step_counters(net)      # a prefill's counts are not a step's
        if do_sample:
            # position-addressed randomness (sampling_keys): the first
            # sampled token lands at cache position `length`
            key = jax.random.fold_in(key, length)
        nxt = _select_next(logits, do_sample, temperature, top_k, top_p,
                           key)
        return nxt, _flatten(caches)

    if keeps_row_state(net.config):
        prefill_body.__name__ = prefill_body.__qualname__ = \
            "prefill_state_body"
    return prefill_body


def build_chunk_prefill_body(net, do_sample, top_k, top_p):
    """The CHUNKED prefill body (prefix-cache warm path): run only the
    uncached tail of a prompt — ``ids`` [1, tail_bucket] starting at
    cache position ``pos`` over a block whose [0, pos) slots were
    gathered from shared prefix pages. Same sampling head as the full
    program; the logits row is ``length - 1`` relative to the chunk.
    Tier-1-pinned bitwise-equal to the full-prompt prefill body."""

    def chunk_prefill_body(params, buffers, ids, length, pos, flat_block,
                           temperature, key):
        net.load_functional_state(params, buffers)
        net.eval()
        logits, caches = prefill(
            net, ids, _unflatten(flat_block, net.config), length=length, pos=pos
        )
        step_counters(net)
        if do_sample:
            # same address as the cold path: the sampled token's cache
            # position is pos + length — warm stays bitwise-equal
            key = jax.random.fold_in(key, pos + length)
        nxt = _select_next(logits, do_sample, temperature, top_k, top_p,
                           key)
        return nxt, _flatten(caches)

    return chunk_prefill_body


class _Seq:
    """Host-side state of one running sequence (one slab row)."""

    __slots__ = ("handle", "first", "last_tok", "emitted", "key", "t_tok",
                 "slo_itl", "slo_e2e")

    def __init__(self, handle, first, key=None, slo_itl=None,
                 slo_e2e=None):
        self.handle = handle
        # the first token while the host has not taken it
        # (ServingEngine._first_token): what the admission holds (its
        # prefill's output on the device, or a remote prefill's
        # integer), the admission's start on engine.clock and the
        # request's TTFT histogram; None from then on
        self.first = first
        self.last_tok = None
        self.emitted = 0  # _append counts (prefill's first token too)
        # the request's base PRNG key (sampling_keys derivation) as a
        # host array — decode steps stack the active rows' keys
        self.key = key
        # engine.clock when the row's newest token reached the host:
        # where its next inter-token sample starts
        self.t_tok = None
        # per-SLO-class bound histogram children, resolved ONCE at
        # admission (observability.slo): the decode hot loop observes
        # straight into them — zero per-token label resolution, the
        # same pinning discipline as the _traced_live gate
        self.slo_itl = slo_itl
        self.slo_e2e = slo_e2e

    @property
    def pos(self):
        # cache position of the token being fed next step: the last
        # emitted token sits at prompt_len + emitted - 1
        return self.handle.request.prompt_len + self.emitted - 1


class _Launched:
    """What was launched and the host has not read yet: one decode
    program and the prefills of the rows admitted since its launch.
    ``seqs`` holds, by slot, the ``_Seq`` OBJECTS whose next token is
    on the device (None: the row was fed nothing): a row that was
    finished while its step ran, by a deadline, a shed or an EOS found
    one step late, has its lagged token dropped by identity, also when
    its slot was admitted again meanwhile. An admission enters its new
    ``_Seq`` at its row (over a finished occupant the step was launched
    for) and its slot into ``admitted``: the prefill is that row's step
    in flight. ``nxt`` and ``counted`` are the decode program's device
    outputs and die with the read (None in a record that admissions
    alone made, when no step was in flight); ``feed`` is what the next
    launch takes as ``prev``: ``nxt``, with every admitted row's first
    token written into its place by the adopt program."""

    __slots__ = ("nxt", "counted", "seqs", "step", "feed", "admitted")

    def __init__(self, nxt, counted, seqs, step):
        self.nxt = nxt
        self.counted = counted
        self.seqs = seqs
        self.step = step
        self.feed = nxt
        self.admitted = []


class _RequestPhase(profiler.RecordEvent):
    """A phase of the driver thread that serves one request, in one
    call for both clocks: the profiler span ``serving::<name>`` with
    the request's ``rid`` among its stats (what varies stays out of the
    name) and, where ``span`` (a dict of attributes) asks for it, the
    request's own span ``engine.<name>`` over the same interval, under
    ``parent`` (the request's trace where None). A request that is
    sampled out gets no span of its own."""

    def __init__(self, name, handle, span=None, parent=None, **stats):
        super().__init__(f"serving::{name}",
                         rid=handle.request.request_id, **stats)
        self._span = None if span is None else get_tracer().start_span(
            f"engine.{name}", handle.trace if parent is None else parent,
            **span)

    def __exit__(self, exc_type, exc, tb):
        self.end()
        if self._span is not None:
            # the request's spans are all admission's
            self._span.finish(
                **({"error": "admission_error"} if exc_type else {}))
        return False


class ServingEngine:
    """Continuous-batching serving over a Llama-family causal LM.

    ``max_batch_size`` is the decode slab's row count (in-flight cap);
    ``max_seq_len`` the per-row cache capacity (prompt + generated).
    Weights are snapshotted at construction — serving a training net
    does not race updates. Greedy by default; ``do_sample=True`` with
    temperature/top_k/top_p reuses ``generate()``'s sampling head with
    a per-step PRNG fold so streams stay reproducible per ``seed``.
    """

    def __init__(self, net, *, max_batch_size=8, max_seq_len=256,
                 cache_dtype=None, do_sample=False, temperature=1.0,
                 top_k=0, top_p=1.0, seed=0, min_bucket=16,
                 max_queue_size=64, max_tokens_in_flight=None,
                 scheduler=None, metrics=None, pool=None,
                 clock=time.monotonic, recompile_guard_max=None,
                 weights_version=None, reload_template=None,
                 speculative=None, sessions=None):
        cfg = net.config
        self.net = net
        self.config = cfg
        # routing-tier identity: which weights this engine serves.
        # `generation` counts in-place weight swaps (live reload bumps
        # it); `weights_version` names the checkpoint. A fleet router
        # reads both off the replica status JSON.
        self.generation = 0
        self.weights_version = (
            "v0" if weights_version is None else str(weights_version)
        )
        # live reload state: a prepared swap waits here until no
        # request is in flight (admission pauses meanwhile, so every
        # request runs under exactly one weights version)
        self._pending_swap = None
        self.reload_in_progress = False
        self.last_reload_step = None
        self._reload_template = reload_template
        # AOT warmup bookkeeping: programs compiled (or cache-loaded)
        # before first traffic, and how many came from the persistent
        # compile cache (the /healthz `compile_cache_hits` field)
        self._warmed = set()
        self.compile_cache_hits = 0
        # per-program HBM footprint table (memory_lint estimate + XLA
        # memory_analysis where available), filled by warmup() and
        # surfaced as /healthz `memory` + the
        # paddle_serving_program_peak_bytes gauge family
        self.program_memory = {}
        self.max_batch_size = int(max_batch_size)
        self.max_seq_len = int(max_seq_len)
        self.clock = clock
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p) if top_p is not None else 1.0
        self.max_tokens_in_flight = max_tokens_in_flight
        self.pool = pool or KVCachePool(
            cfg, dtype=cache_dtype or DEFAULT_CACHE_DTYPE,
            min_bucket=min_bucket, max_seq_len=self.max_seq_len,
        )
        self.cache_dtype = self.pool.dtype
        if not keeps_kv_pairs(cfg):
            # a net that states another cache than K and V per head
            # (a latent page, a state a row) is served plainly or not
            # at all
            asked = [what for what, on in self._kv_pair_features(
                speculative).items() if on]
            # a net may state both (a latent page in one layer, a state
            # a row in another): it is told both
            reasons = []
            if asked and keeps_row_state(cfg):
                reasons.append(
                    f"{type(net).__name__} keeps a state a row beside "
                    f"its pages; " + "; ".join(
                        f"{what} is not supported over it: "
                        f"{_ROW_STATE_REFUSALS[what]}" for what in asked)
                )
            # ... or neither and still no K/V pair in every layer (a
            # layer that keeps nothing at all): the same sentence
            if asked and (not token_arrays_are_kv_pairs(cfg)
                          or not reasons):
                reasons.append(
                    f"{type(net).__name__} states a cache that is not "
                    f"K and V per head; {', '.join(asked)} "
                    f"{'is' if len(asked) == 1 else 'are'} written for "
                    f"K/V pairs and not supported over it"
                )
            if reasons:
                raise ValueError(". ".join(reasons))
        self.scheduler = scheduler or Scheduler(
            max_queue_size=max_queue_size, clock=clock
        )
        self.metrics = metrics or ServingMetrics()
        # conversation bookkeeping (serving.sessions.SessionStore):
        # True builds a default store; a caller-built store passes
        # through; None serves request-at-a-time exactly as before
        if sessions is True:
            from .sessions import SessionStore

            sessions = SessionStore(clock=clock)
        # explicit None/False check: an EMPTY store is len()-falsy
        self.sessions = None if sessions in (None, False) else sessions
        # weight snapshot: serving uses these, not live layer attrs
        self._params = {k: p.value for k, p in net.named_parameters()}
        self._buffers = {k: b.value for k, b in net.named_buffers()}
        self._was_training = net.training
        self._init_kv_backend()
        self._seqs = [None] * self.max_batch_size
        self._key = jax.random.PRNGKey(seed)  # warmup example key shape
        self.keys = SamplingKeySource(seed)
        self.step_count = 0
        # every program that rewrites the KV state donates it, on every
        # backend: the tests run the programs users get
        self._prefill_fns = {}   # bucket -> jitted fn
        self._adopt_fns = {}     # bucket -> jitted fn
        self._spec_gather_fn = None  # lazy (speculative verify only)
        self._decode_fn = jax.jit(self._decode_body, donate_argnums=(3,))
        self._traced = set()
        # count of in-flight requests that carry an open decode span —
        # the decode hot path checks this ONE integer and, when zero
        # (tracing off / sampled out), allocates no span machinery
        self._traced_live = 0
        # engine.clock at the return of the last decode step's blocking
        # read, None once the engine is idle: where the next
        # metrics.host_gap sample starts
        self._read_done = None
        # this iteration admitted while other rows were resident: its
        # host_gap sample is a metrics.admit_hold sample too
        self._admit_held = False
        # what is launched and not yet read (_Launched), None when
        # nothing is: before the first admission, under speculation,
        # on an idle engine
        self._in_flight = None
        # what the adopt program is handed where it has no first token
        # to place: the tokens to merge into when no step is in flight,
        # and the token of an adoption that admits no row (a
        # speculative round's block, a restored page)
        self._no_feed = jnp.zeros((self.max_batch_size,), jnp.int32)
        self._no_first = jnp.zeros((1,), jnp.int32)
        self._closed = False
        # runtime lint guard: the whole engine design exists so that
        # admission/retirement NEVER recompile — if compile caches grow
        # anyway (bucket sprawl, decode shape drift), the guard turns
        # the silent latency spike into a finding + a chrome-trace span
        from ..analysis.trace_guard import TraceGuard

        if recompile_guard_max is None:
            # expected steady state: one prefill + one adopt program per
            # power-of-two bucket, one decode program; anything well
            # past that is a storm. Bucket count comes from the POOL's
            # geometry (a caller-supplied pool may use a different
            # min_bucket/max_seq_len than this engine's defaults).
            import math

            pool_min = getattr(self.pool, "min_bucket", min_bucket)
            pool_max = getattr(self.pool, "max_seq_len", None) \
                or self.max_seq_len
            buckets = 1 + max(
                0, int(math.log2(max(pool_max, 1)))
                - int(math.log2(max(pool_min, 1)))
            )
            recompile_guard_max = max(4, buckets + 2)
        self.trace_guard = TraceGuard(max_compiles=recompile_guard_max)
        self.trace_guard.on_fire(self._on_guard_fire)
        self.trace_guard.watch("serving::decode_step", self._decode_fn)
        # speculative decoding (serving.speculative): when bound, the
        # decode phase runs propose+verify rounds instead of the fused
        # per-token step
        self.speculative = speculative
        if speculative is not None:
            speculative.bind(self)

    def _kv_pair_features(self, speculative):
        """The options asked for that exist for K/V-pair caches only,
        by name: refused at construction over any other layout."""
        return {
            "int8 cache storage": self.cache_dtype == jnp.int8,
            "speculative decoding": speculative is not None,
        }

    def _init_kv_backend(self):
        """Allocate the resident decode KV state — the slab here
        ([N, S_max] rows claimed per request); the paged engine
        overrides with a page arena + per-row page tables."""
        self._flat = _flatten(
            self.pool.alloc_slab_arrays(self.max_batch_size,
                                        self.max_seq_len)
        )
        self._slab = self.pool.register_slab(self.max_batch_size,
                                             self.max_seq_len)

    def _on_guard_fire(self, finding):
        """A recompile storm at runtime: emit a lint-guard span so the
        storm shows in chrome traces instead of only as a latency
        spike, and count it on the engine's metrics."""
        profiler.record_span(
            f"serving::lint_guard::{finding.rule}", 0.0, kind="lint"
        )
        self.metrics.guard_fires.inc(label=finding.graph)

    # ------------------------------------------------- compiled programs
    def _decode_body(self, params, buffers, tok, flat, pos, temperature,
                     key, prev, from_host):
        # this body runs only while jit traces it, which leaves tracers
        # in the net: _run puts the weights back after the call. A
        # LATER trace (``prev`` comes back from a program placed over a
        # mesh where the first launch had an upload) must do so too
        self._traced.discard(("decode",))
        self.net.load_functional_state(params, buffers)
        self.net.eval()
        # a continuing row is fed the token the step before sampled,
        # which never visited the host; a row admitted since that
        # launch (and every row when nothing was in flight) its
        # ``tok`` from the host
        tok = jnp.where(from_host, tok, prev)
        logits, caches = decode_step(
            self.net, tok[:, None], _unflatten(flat, self.config), pos
        )
        if self.do_sample:
            # `key` is [B, 2] — every row carries its request's base
            # key; the token sampled this step lands at pos + 1, so
            # fold per row (the sampling_keys position address)
            key = jax.vmap(jax.random.fold_in)(key, pos + 1)
        nxt = _select_next(logits, self.do_sample, temperature,
                           self.top_k, self.top_p, key)
        return nxt, _flatten(caches), step_counters(self.net)

    def _prefill_fn(self, bucket):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        body = build_prefill_body(self.net, self.do_sample, self.top_k,
                                  self.top_p)
        fn = jax.jit(
            body, donate_argnums=(4,)
        )
        self._prefill_fns[bucket] = fn
        self.trace_guard.record_compile(
            "serving::prefill", bucket, origin="serving/engine.py"
        )
        return fn

    def _adopt_fn(self, bucket):
        """Copy a prefilled [1, bucket] block into decode row ``slot``
        and the prefill's ``first`` token ``[1]`` into the row's place
        of ``feed``, the ``[max_batch]`` tokens the next decode launch
        takes as ``prev``: the new row enters the batch whole, its
        token never leaving the device. Returns the slab's arrays and,
        last, the feed."""
        fn = self._adopt_fns.get(bucket)
        if fn is not None:
            return fn

        def adopt_body(flat_decode, flat_block, slot, feed, first):
            from ..quantization.kv import adopt_into_slab

            return [
                adopt_into_slab(d, b, slot)
                for d, b in zip(flat_decode, flat_block)
            ] + [jax.lax.dynamic_update_slice(feed, first, (slot,))]

        fn = jax.jit(
            adopt_body, donate_argnums=(0,)
        )
        self._adopt_fns[bucket] = fn
        self.trace_guard.record_compile(
            "serving::adopt", bucket, origin="serving/engine.py"
        )
        return fn

    def _adopt(self, bucket, flat_block, *where, first=None):
        """Run ``bucket``'s adopt program over the resident KV state:
        ``flat_block`` lands at ``where`` (``_adopt_fn``'s arguments
        between the block and the feed, a row's last) and ``first``,
        an admission's first token (on the device, or a remote
        prefill's integer), in that row's place of the tokens the next
        launch is fed. Returns that feed; an adoption that admits no
        row hands over no token and drops it."""
        if first is None:
            first = self._no_first
        elif not isinstance(first, jax.Array):
            first = jnp.asarray([first], jnp.int32)
        fl = self._in_flight
        out = self._run(
            ("adopt", bucket), self._adopt_fn(bucket),
            self._flat, flat_block, *where,
            self._no_feed if fl is None else fl.feed, first,
        )
        self._flat = out[:-1]
        return out[-1]

    def _restore_net_state(self):
        """Put the imperative net back in concrete serving state —
        required after ANYTHING that traced a program body (execution
        tracing or ``.lower()``), which swaps tracers into the Layer
        objects, and after a weight swap, so later snapshots/templates
        see what the engine serves."""
        self.net.load_functional_state(self._params, self._buffers)
        if self._was_training:
            self.net.train()
        else:
            self.net.eval()

    def _run(self, trace_key, fn, *args):
        """Invoke a jitted program; after its FIRST trace, restore the
        net's concrete weights/mode (tracing swaps tracers into the
        imperative Layer objects — generate()'s write-back pattern)."""
        out = fn(*args)
        if trace_key not in self._traced:
            self._traced.add(trace_key)
            self._restore_net_state()
        return out

    def _next_key(self):
        """The admitted request's base PRNG key — one per admission,
        derived by position-addressable fold (sampling_keys), NOT a
        mutable split chain: the same workload in the same order gets
        the same keys on every engine geometry."""
        if not self.do_sample:
            # greedy ignores the key entirely (argmax head) — hand the
            # constant placeholder instead of a per-admission derivation
            return self._key
        return self.keys.next_request_key()

    # ------------------------------------------- speculative backend seams
    #
    # speculative.SpeculativeDecoder drives its one-launch verify
    # through these four hooks. The slab backend is trivial — every row
    # permanently owns the full [0, S_max) span, so reserve always
    # succeeds and rollback is free (rejected-tail KV sits behind the
    # position mask until the row's own later writes overwrite it).
    # The paged engine overrides all four with demand-grown pages.

    def _spec_reserve(self, slot, hi):
        """Guarantee backend KV capacity for verify writes up to cache
        position ``hi``; returns the highest position actually held
        (may clamp below ``hi`` under page pressure)."""
        return min(hi, self.max_seq_len - 1)

    def _spec_gather_prog(self):
        """The (jitted, uncompiled) slab gather program — one row of
        the slab materialized as a prefill-layout ``[1, S_max]``
        block. Built lazily so warmup and first-use share one
        program object."""
        fn = self._spec_gather_fn
        if fn is None:
            from ..quantization.kv import slab_row_block

            def spec_gather_body(flat, s):
                return [slab_row_block(a, s) for a in flat]

            fn = self._spec_gather_fn = jax.jit(spec_gather_body)
            self.trace_guard.record_compile(
                "serving::spec_gather", self.max_seq_len,
                origin="serving/engine.py",
            )
        return fn

    def _spec_gather(self, slot, hi):
        """Materialize row ``slot``'s KV as a prefill-layout ``[1, W]``
        block covering positions [0, ``hi``]; returns
        ``(flat_block, W)``."""
        fn = self._spec_gather_prog()
        return fn(self._flat, jnp.int32(slot)), self.max_seq_len

    def _spec_adopt(self, slot, new_block, width, pos):
        """Land a verify-updated block back as row ``slot``'s KV — the
        same adopt program admission uses, at bucket ``width``
        (positions < ``pos`` came back unchanged; [pos, pos+K] carry
        the verify's writes)."""
        self._adopt(width, new_block, jnp.int32(slot))

    def _spec_rollback(self, slot, new_pos):
        """Drop verify writes past the accepted span (the row's next
        token feeds at ``new_pos``). Free on the slab; the paged
        engine releases the rejected tail's demand-claimed pages."""

    # ---------------------------------------------------------- requests
    def _too_long(self, req):
        """Reject-at-submit gate: a request no amount of draining could
        ever admit. Subclasses extend it with their backend's own hard
        ceiling (e.g. the whole page arena)."""
        return req.total_tokens > self.max_seq_len or (
            self.max_tokens_in_flight is not None
            and req.total_tokens > self.max_tokens_in_flight
        )

    def submit(self, input_ids, max_new_tokens=32, *, eos_token_id=None,
               priority=0, deadline_s=None, slo_class=None,
               session_id=None, on_token=None, on_event=None):
        """Enqueue one request; always returns a RequestHandle (status
        REJECTED with ``.reason`` set on backpressure — submit never
        blocks and never throws for load reasons).

        ``slo_class`` names the request's SLO traffic class
        (``interactive`` when None; see ``observability.slo``) — it
        labels the TTFT/ITL/E2E histograms this request lands in.
        ``session_id`` marks the request as one turn of a conversation
        (``serving.sessions``): the session store is touched here and
        records the finished turn's full token chain — never affecting
        the token stream itself. ``on_token(tok, handle)`` streams each
        emitted token as the engine produces it; ``on_event(handle)``
        fires exactly once at the terminal transition (including
        submit-time rejects — a stream consumer always gets an
        ending)."""
        req = Request(
            input_ids, max_new_tokens, eos_token_id=eos_token_id,
            priority=priority, deadline_s=deadline_s,
            slo_class=slo_class, session_id=session_id,
        )
        self.metrics.submitted.inc()
        if session_id is not None and self.sessions is not None \
                and not self._closed:
            self.sessions.touch(session_id)
        if self._closed:
            h = RequestHandle(req, on_token=on_token, on_event=on_event)
            h.submit_time = h.finish_time = self.clock()
            h.status = REJECTED
            h.reason = REASON_ENGINE_CLOSED
            self.metrics.rejected.inc(label=REASON_ENGINE_CLOSED)
            h._fire_terminal()
            return h
        if self._too_long(req):
            h = RequestHandle(req, on_token=on_token, on_event=on_event)
            h.submit_time = h.finish_time = self.clock()
            h.status = REJECTED
            h.reason = REASON_TOO_LONG
            self.metrics.rejected.inc(label=REASON_TOO_LONG)
            h._fire_terminal()
            return h
        try:
            return self.scheduler.submit(req, on_token=on_token,
                                         on_event=on_event)
        except RejectedError as e:
            self.metrics.rejected.inc(label=e.reason)
            return e.handle

    # --------------------------------------------------------- the loop
    @property
    def active_slots(self):
        return sum(1 for s in self._seqs if s is not None)

    def _tokens_in_flight(self):
        return sum(
            s.handle.request.total_tokens
            for s in self._seqs if s is not None
        )

    def _release_slot(self, slot):
        """Return slot ``slot``'s KV residency to the pool (slab row
        here; row + claimed pages in the paged engine)."""
        if self.speculative is not None:
            self.speculative.reset_slot(slot)
        self._slab.release(slot)

    def _finish(self, slot, status, reason=None):
        seq = self._seqs[slot]
        h = seq.handle
        now = self.clock()
        h.status = status
        h.reason = reason
        h.finish_time = now
        h.finished_step = self.step_count
        if status == DONE:
            self.metrics.completed.inc()
        elif status == TIMEOUT:
            self.metrics.timeouts.inc()
        tid = None if h.trace is None else h.trace.trace_id
        (seq.slo_e2e or self.metrics.e2e).observe(
            now - h.submit_time, trace_id=tid
        )
        sp = h._decode_span
        if sp is not None:
            h._decode_span = None
            self._traced_live -= 1
            sp.finish(status=status, tokens=len(h.tokens),
                      **({"error": reason} if reason else {}))
        sid = h.request.session_id
        if sid is not None and self.sessions is not None \
                and not self._closed:
            # the finished turn's FULL conversation ids (prompt +
            # answer) — the exact chain turn N+1's prompt extends
            self.sessions.note_turn(sid, h.output_ids)
        self._seqs[slot] = None
        if not self.active_slots:
            # the engine goes idle: what passes until the next decode
            # program is no host gap, and a step still in flight (the
            # one launched before an EOS was seen) is for no one
            self._read_done = None
            self._in_flight = None
        self._release_slot(slot)
        h._fire_terminal()

    def _append(self, slot, tok):
        seq = self._seqs[slot]
        h = seq.handle
        h.tokens.append(int(tok))
        seq.last_tok = int(tok)
        seq.emitted += 1
        self.metrics.tokens_out.inc()
        h._fire_token(tok)
        req = h.request
        if req.eos_token_id is not None and int(tok) == req.eos_token_id:
            self._finish(slot, DONE)
        elif seq.emitted >= req.max_new_tokens:
            self._finish(slot, DONE)

    def _trace_admitted(self, handle, slot, wait):
        """Admission-time spans under the request's trace context: the
        scheduler-measured queue wait rendered retroactively (the span
        duration IS ``wait`` — the same number the ``queue_wait``
        histogram observed), and the ONE open decode span whose bounded
        event ring the step loop feeds. Zero allocations when the
        request is sampled out (``handle.trace is None``)."""
        tspan = handle.trace
        if tspan is None:
            return
        tr = get_tracer()
        tr.record_span("engine.queue_wait", tspan, wait)
        handle._decode_span = tr.start_span("engine.decode", tspan,
                                            slot=slot)
        if handle._decode_span is not None:
            self._traced_live += 1

    def _admit_one(self, handle):
        req = handle.request
        now = self.clock()
        bucket = self.pool.bucket_for(req.prompt_len)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, : req.prompt_len] = req.input_ids
        blk = self.pool.alloc(req.prompt_len)
        # claim the slot LAST, with a release guard: an exception out of
        # admission must never strand a claimed slot (a 1-slot engine
        # would wedge forever)
        slot = self._slab.claim()
        assert slot is not None  # caller checked free_slots
        key = self._next_key()
        t_pre = self.clock()
        try:
            # the request's engine.prefill span covers what the phase
            # does: the launches of the prefill and of its adoption
            with _RequestPhase("prefill", handle, bucket=bucket,
                               span={"mode": "local", "bucket": bucket}):
                first, new_flat = self._run(
                    ("prefill", bucket), self._prefill_fn(bucket),
                    self._params, self._buffers, jnp.asarray(ids),
                    jnp.int32(req.prompt_len), _flatten(blk.caches),
                    jnp.float32(self.temperature), key,
                )
                blk.caches = _unflatten(new_flat, self.config)
                with _RequestPhase("adopt", handle, bucket=bucket):
                    feed = self._adopt(bucket, new_flat, jnp.int32(slot),
                                       first=first)
        except BaseException:
            self._slab.release(slot)
            # the failed call may already have consumed the block's
            # donated buffers — recycling them would poison the bucket's
            # freelist; drop the block instead
            self.pool.discard(blk)
            raise
        self.pool.free(blk)
        self._seat(slot, handle, first, feed, key, now, t_pre)

    def _seat(self, slot, handle, first, feed, key, now, t_pre):
        """The end of an admission, whose programs are launched: the
        request is RUNNING in row ``slot``, and its prefill is that
        row's step in flight. Its ``first`` token stays on the device:
        the adopt program wrote it into the row's place of ``feed``,
        which the next decode launch takes as ``prev``, and the new
        ``_Seq`` is entered into the record of what is in flight (one
        is made when nothing is), so that ``_launch_pos`` feeds the row
        at ``prompt_len`` from the device, or nothing where
        ``max_new_tokens`` is 1, and ``_decode_once`` reads the token
        after that launch. Where the device does not have the token (a
        remote prefill handed an integer) or the host needs it before
        any launch (speculation proposes from it) it is taken here, and
        the row is fed from the host like any row that is not in
        flight: the input decides, no option does."""
        req = handle.request
        handle.status = RUNNING
        handle.weights_version = self.weights_version
        handle.admit_time = now
        handle.admitted_step = self.step_count
        wait = now - handle.submit_time
        tid = None if handle.trace is None else handle.trace.trace_id
        self.metrics.admitted.inc()
        self.metrics.prefill_tokens.inc(req.prompt_len)
        self.metrics.queue_wait.observe(wait, trace_id=tid)
        slo_ttft, slo_itl, slo_e2e = self.metrics.slo_children(
            req.slo_class
        )
        self._trace_admitted(handle, slot, wait)
        seq = self._seqs[slot] = _Seq(
            handle, (first, t_pre, slo_ttft), key=np.asarray(key),
            slo_itl=slo_itl, slo_e2e=slo_e2e)
        if self.speculative is not None or not isinstance(first, jax.Array):
            self._first_token(slot, seq)
            return
        fl = self._in_flight
        if fl is None:
            fl = self._in_flight = _Launched(
                None, None, [None] * self.max_batch_size, self.step_count)
        fl.feed = feed
        fl.seqs[slot] = seq
        fl.admitted.append(slot)

    def _first_token(self, slot, seq, in_flight=False):
        """Take row ``slot``'s first token to the host and emit it: the
        far end of the ``prefill`` and TTFT samples. ``in_flight``: the
        read waits for the prefill program, with the next decode step
        queued behind it, and what the host does from its return to
        the next launch is that launch's ``host_gap``. What the
        prefill or its adoption raised on the device surfaces only
        here: the request ends as an admission that raised at its
        launch does (``admission_error``), its row and pages go back,
        and the other rows go on."""
        first, t_pre, slo_ttft = seq.first
        seq.first = None
        h = seq.handle
        try:
            with profiler.RecordEvent("serving::first_token",
                                      rid=h.request.request_id):
                tok = int(np.asarray(first).reshape(-1)[0])
        except Exception as e:
            self.metrics.rejected.inc(label="admission_error")
            self._finish(slot, REJECTED,
                         reason=f"admission_error:{type(e).__name__}")
            return
        now = h.first_token_time = seq.t_tok = self.clock()
        if in_flight:
            self._read_done = now
        self.metrics.prefill.observe(now - t_pre)
        slo_ttft.observe(
            now - h.submit_time,
            trace_id=None if h.trace is None else h.trace.trace_id)
        self._append(slot, tok)

    def _decode_extra(self, fed):
        """Extra positional decode-step inputs between the KV state and
        ``pos`` (the paged engine passes its page tables here, with the
        table of an active row that is not ``fed`` cleared)."""
        return ()

    def _read_span(self, pos):
        """Cache columns a row's attention reads in a decode step
        launched at the rows' positions ``pos`` (numpy ``[B]``): a slab
        row is read whole."""
        return self.max_seq_len

    def _has_capacity(self):
        return self._slab.free_slots > 0

    def _admission_budget(self):
        """Token budget the next admission must fit (None = no cap).
        The paged engine folds free-page capacity in here too."""
        if self.max_tokens_in_flight is None:
            return None
        return self.max_tokens_in_flight - self._tokens_in_flight()

    def _max_admissions_per_step(self):
        """Prefills allowed per engine step. Unbounded for the slab
        engine (its historical behavior); the paged engine caps it —
        the prefill/decode disaggregation lever."""
        return None

    def _admission_fits(self):
        """Optional per-request feasibility predicate handed to the
        scheduler's pop (None = budget-only admission). The prefix-
        caching paged engine supplies one: a warm request's page need
        depends on how much of its prompt the cache covers, which a
        scalar token budget cannot express."""
        return None

    def step(self):
        """One engine iteration: retire expired, admit into free slots
        (launches alone, nothing is read for it), launch one decode
        step over the whole resident KV state, read the one launched
        before it and then the first tokens of the rows just admitted.
        Each phase is a ``RecordEvent`` span with a fixed name (a
        phase's own time is its span less the spans inside it; what
        varies, the bucket, the request and the step, is a stat):
        ``serving::admit`` (with the admission's ``gather``,
        ``prefill``, ``chunk_prefill`` and ``adopt`` inside it, each
        the launch of its program), ``serving::decode_inputs``,
        ``serving::decode_step`` (own time: the launch of step n+1
        alone; ``serving::read``, the blocking read of step n, is
        inside it), ``serving::emit`` (step n's tokens),
        ``serving::first_token`` (the blocking read of an admitted
        row's first token: the wait for its prefill program),
        ``serving::step_tail``."""
        if self._closed:
            raise RuntimeError(f"{type(self).__name__} is closed")
        with profiler.RecordEvent("serving::step", step=self.step_count):
            # a staged weight swap applies the moment nothing is in
            # flight
            self._maybe_apply_reload()
            now = self.clock()
            # running sequences past their deadline free their slot NOW
            for i, seq in enumerate(self._seqs):
                if seq is None:
                    continue
                dl = self.scheduler.deadline_of(seq.handle)
                if dl is not None and now > dl:
                    self._finish(i, TIMEOUT, reason=REASON_TIMEOUT)
            # queued requests whose deadline passed never run at all
            self.scheduler.sweep_expired()
            with profiler.RecordEvent("serving::admit"):
                self._admit()
            self._decode_once()
            with profiler.RecordEvent("serving::step_tail"):
                # the last in-flight request may have finished this
                # step — a pending swap must not wait for another
                # external step() call
                self._maybe_apply_reload()
                self.step_count += 1
                # poll jit-internal compile caches (decode shape drift
                # is invisible to the bucket maps above); fires
                # _on_guard_fire
                self.trace_guard.check()
                self.metrics.observe_step(self.scheduler.depth,
                                          self.active_slots)

    def _admit(self):
        """Admission: fill free capacity in priority-FIFO order under
        the in-flight token cap (and the per-step prefill cap, when
        set), against the host's state as it stands with a step in
        flight: nothing is read first. That state lags the device by
        at most that step and only to the safe side: a row that ends
        in the step in flight holds its slot, its pages and its share
        of the token budget for one more iteration."""
        cap = self._max_admissions_per_step()
        admitted = 0
        self._admit_held = False
        # a pending reload pauses admission: in-flight requests drain
        # on the OLD weights, queued ones wait for the swap — zero
        # dropped, one weights version per request
        while self._pending_swap is None and self._has_capacity() and (
            cap is None or admitted < cap
        ):
            handle = self.scheduler.pop_next(self._admission_budget(),
                                             fits=self._admission_fits())
            if handle is None:
                break
            if self.active_slots:
                self._admit_held = True
            try:
                self._admit_one(handle)
            except BaseException as e:
                # the handle was already popped — resolve it before
                # propagating, or a caller polling h.finished waits
                # forever on a request no queue holds anymore
                handle.status = REJECTED
                handle.reason = f"admission_error:{type(e).__name__}"
                handle.finish_time = self.clock()
                self.metrics.rejected.inc(label="admission_error")
                handle._fire_terminal()
                raise
            admitted += 1
        # single metrics channel for queued-expiry, whether the sweep or
        # a lazy pop_next expired the request (a deadline can pass
        # mid-step while a prefill compiles)
        for _ in self.scheduler.drain_timed_out():
            self.metrics.timeouts.inc()

    def _launch_pos(self, slot):
        """The cache position the next decode launch feeds row ``slot``
        at: its host position, one further while its token of the step
        in flight is unread, be that step a decode step or, for a row
        just admitted, its prefill (``prompt_len``). None where the
        launch feeds nothing: a free row, or a row whose unread token
        is its last by ``max_new_tokens`` (known without seeing it, so
        a length-bound request never runs a step too many)."""
        seq = self._seqs[slot]
        if seq is None:
            return None
        fl = self._in_flight
        lag = fl is not None and fl.seqs[slot] is seq
        if seq.emitted + lag >= seq.handle.request.max_new_tokens:
            return None
        return seq.pos + lag

    def _decode_once(self):
        """The decode phase of one iteration, with at most one step in
        flight: launch the fused step n+1 over every row, THEN read the
        tokens of step n and emit them, so that the read blocks with
        the next program already queued behind it on the device, and
        emit, the step's tail, the front end's lock, page growth and
        the next inputs all run under a running program. A step costs
        max(program, host), not their sum.

        A continuing row's input token stays on the device (step n's
        ``nxt`` feeds step n+1 through ``where(from_host, tok, prev)``);
        positions, keys and page tables the host knows one step ahead.
        A row whose unread token is its last is fed nothing; a row that
        ends on EOS is found one step late, and its extra step (the EOS
        token's true KV at the next position of its own pages) is
        dropped with it. Free and unfed rows ride along as masked
        garbage (their writes land on slots adoption overwrites).

        An iteration that admitted is the same iteration with two more
        programs queued: step n+1 is launched behind step n, the
        prefill and its adoption, and takes the new row's first token
        from the adopt program's ``feed``; step n is read from ITS OWN
        output, which no prefill stands before, and emitted; then the
        first token of each row admitted is read (``_first_token``),
        which waits for the prefill with step n+1 queued behind it. A
        resident stream's gap across an admission is the prefill, the
        adoption and one decode program. When no decode step is in
        flight (the first admission of a busy stretch) there is none to
        read and the order is the serial one: launch, then the first
        token. Speculation runs its own rounds and never has a step in
        flight."""
        if not self.active_slots:
            return
        if self.speculative is not None:
            # propose + one-launch verify per row instead of the fused
            # per-token step (speculative.py)
            self.speculative.decode_once(self)
            return
        prev = self._in_flight
        with profiler.RecordEvent("serving::decode_inputs"):
            tok = np.zeros((self.max_batch_size,), np.int32)
            pos = np.zeros((self.max_batch_size,), np.int32)
            keys = np.zeros((self.max_batch_size, 2), np.uint32)
            from_host = np.ones((self.max_batch_size,), bool)
            fed = [None] * self.max_batch_size
            for i, seq in enumerate(self._seqs):
                p = self._launch_pos(i)
                if p is None:
                    continue
                fed[i] = seq
                pos[i] = p
                keys[i] = seq.key
                if p == seq.pos:
                    tok[i] = seq.last_tok
                else:
                    from_host[i] = False
            launch = any(seq is not None for seq in fed)
            if launch:
                self.metrics.resident_tokens.observe(int(pos.sum()))
                self.metrics.span_tokens.observe(self._read_span(pos))
                tok = jnp.asarray(tok)
                inputs = (
                    tok, self._flat, *self._decode_extra(fed),
                    jnp.asarray(pos), jnp.float32(self.temperature),
                    jnp.asarray(keys),
                    tok if prev is None else prev.feed,
                    jnp.asarray(from_host),
                )
        with profiler.RecordEvent("serving::decode_step",
                                  step=self.step_count):
            # set before the read: a row that ends there on EOS as the
            # last one drops the step just launched (_finish)
            self._in_flight = None
            if launch:
                if self._read_done is not None:
                    gap = self.clock() - self._read_done
                    self.metrics.host_gap.observe(gap)
                    if self._admit_held:
                        self.metrics.admit_hold.observe(gap)
                    self._read_done = None
                nxt, self._flat, counted = self._run(
                    ("decode",), self._decode_fn,
                    self._params, self._buffers, *inputs,
                )
                self._in_flight = _Launched(nxt, counted, fed,
                                            self.step_count)
                if prev is not None and prev.nxt is not None:
                    self.metrics.steps_overlapped.inc()
                # the uploads die here, with the launch, as temporaries
                # would: freeing a device array lets go of the GIL, and
                # after emit that hands it to every woken stream thread
                # while the front end's lock is still held. The step's
                # outputs live in the record alone, until their read
                del inputs, tok, nxt, counted
            if prev is not None:
                # launched from: it dies here too, as the uploads did
                prev.feed = None
                toks = None if prev.nxt is None else self._read(prev)
        if prev is None:
            return
        if toks is not None:
            self._emit(prev, toks)
        for slot in prev.admitted:
            seq = prev.seqs[slot]
            if self._seqs[slot] is seq:     # not shed since
                self._first_token(slot, seq, in_flight=True)

    def _read(self, launched):
        """Block until a launched step's tokens are on the host: the
        one place the driver waits for the device. With the next step
        already launched the device goes on under everything the host
        does until it comes back here. The span ``serving::read``
        carries the number of the step it reads, as that step's launch
        (``serving::decode_step``) did."""
        t0 = self.clock()
        with profiler.RecordEvent("serving::read", step=launched.step):
            toks = np.asarray(launched.nxt)
        self.metrics.observe_step_counters(launched.counted)
        # the device arrays die here, right after the read (see the
        # launch for why not later)
        launched.nxt = launched.counted = None
        self._read_done = self.clock()
        self.metrics.read_wait.observe(self._read_done - t0)
        return toks

    def _emit(self, launched, toks):
        """Hand a read step's tokens to its rows: the row's inter-token
        sample (read returned to read returned; from its first token's
        time for a row's first decode token), ``_append``, ``on_token``.
        A row that is no longer the one the step was launched for gets
        nothing, and a row admitted since the launch has no token in
        it (``_first_token`` brings its own)."""
        now = self._read_done
        with profiler.RecordEvent("serving::emit"):
            # sampled-out runs never look for a span: this one integer
            # is the whole hot-path cost of request tracing
            traced = self._traced_live
            occ = traced and sum(seq is not None for seq in launched.seqs)
            for i, seq in enumerate(launched.seqs):
                if seq is None or self._seqs[i] is not seq \
                        or seq.first is not None:
                    continue
                dt = now - seq.t_tok
                seq.t_tok = now
                sp = seq.handle._decode_span if traced else None
                if sp is not None:
                    # ONE bounded-ring event per traced request per
                    # step (the O(1)-spans discipline: a 500-step
                    # decode stays one span)
                    sp.event("decode_step", step=launched.step,
                             occupancy=occ, dt_s=dt)
                # per-class child bound at admission: no label
                # resolution (and no allocation) on this per-token path
                (seq.slo_itl or self.metrics.itl).observe(dt)
                self._append(i, toks[i])

    def run_until_idle(self, max_steps=100_000):
        """Drive ``step()`` until queue and slab are empty."""
        steps = 0
        while self.scheduler.depth or self.active_slots:
            if steps >= max_steps:
                raise RuntimeError(
                    f"run_until_idle: not drained after {max_steps} steps"
                    f" (queue={self.scheduler.depth},"
                    f" active={self.active_slots})"
                )
            self.step()
            steps += 1
        return steps

    def generate(self, prompts, max_new_tokens=32, **submit_kwargs):
        """Batch convenience: submit every prompt, drain, and return
        the handles in submit order."""
        handles = [
            self.submit(p, max_new_tokens, **submit_kwargs)
            for p in prompts
        ]
        self.run_until_idle()
        return handles

    # ------------------------------------------------------- live reload
    def prepare_reload(self, ckpt_dir, *, weights_version=None,
                       template_net=None, verify_level="full"):
        """Stage a weight swap from a committed checkpoint directory
        (or a checkpoint root — newest committed step wins): verify the
        manifest/CRCs, load into a template, quantize for serving when
        this engine runs quantized weights, and validate against the
        compiled programs' snapshot. Pure and thread-safe — run it OFF
        the step loop; pass the result to :meth:`commit_reload`.
        Failures come back as a non-ok :class:`~.reload.StagedReload`
        (counted by outcome), never an exception — the engine keeps
        serving the last committed weights."""
        from .reload import prepare_state_swap

        staged = prepare_state_swap(
            self.net, self._params, self._buffers, ckpt_dir,
            weights_version=weights_version,
            template_net=template_net or self._reload_template,
            verify_level=verify_level,
        )
        if not staged.ok:
            self.metrics.reloads.inc(label=staged.outcome)
        return staged

    def commit_reload(self, staged):
        """Hand a prepared swap to the step loop (same single-thread
        discipline as :meth:`step` — the HTTP frontend calls this under
        its driver lock). Applies immediately when nothing is in
        flight; otherwise admission pauses and the swap lands at the
        first step boundary with zero active requests. A staged swap
        committed over a still-pending one supersedes it (newest
        checkpoint wins)."""
        if not staged.ok:
            return staged
        if self._closed:
            staged.ok = False
            staged.outcome = "engine_closed"
            self.metrics.reloads.inc(label="engine_closed")
            return staged
        if self._pending_swap is not None:
            self.metrics.reloads.inc(label="superseded")
        staged.staged_at = self.clock()
        self._pending_swap = staged
        self.reload_in_progress = True
        self._maybe_apply_reload()
        return staged

    def reload_weights(self, ckpt_dir, **kw):
        """prepare + commit in one call (callers on the engine's own
        thread — tests, benches, the launch entrypoint)."""
        return self.commit_reload(self.prepare_reload(ckpt_dir, **kw))

    def _maybe_apply_reload(self):
        if self._pending_swap is not None and self.active_slots == 0:
            self._apply_reload()

    def _apply_reload(self):
        from . import chaos as _chaos

        staged = self._pending_swap
        try:
            # the deterministic kill-mid-swap seam: a fault here must
            # leave the engine fully on the OLD weights (nothing below
            # has mutated yet — the swap is all-or-nothing)
            _chaos.poke("reload.apply", step=staged.step,
                        version=staged.weights_version)
        except BaseException as e:
            self._pending_swap = None
            self.reload_in_progress = False
            staged.ok = False
            staged.outcome = "error"
            staged.error = repr(e)
            self.metrics.reloads.inc(label="error")
            return
        self._params = staged.params
        self._buffers = staged.buffers
        self.weights_version = staged.weights_version
        self.generation += 1
        self.last_reload_step = staged.step
        self._pending_swap = None
        self.reload_in_progress = False
        self._restore_net_state()
        # backend hook: the paged engine flushes its prefix cache here —
        # a post-swap request must never adopt KV computed under the
        # weights that just rotated out
        self._on_weights_swapped()
        # disaggregation stays exact across the rotation: the prefill
        # worker's version-skew refusal now rejects OLD-weights blocks
        tr = getattr(self, "prefill_transport", None)
        if tr is not None and getattr(tr, "expected_weights_version",
                                      None) is not None:
            tr.expected_weights_version = staged.weights_version
        if staged.staged_at is not None:
            pause = self.clock() - staged.staged_at
            self.metrics.reload_ttft_spike.observe(pause)
            # the admission-pause window as a (head-sampled) root span:
            # the reload's worst-case extra TTFT is visible in the same
            # timeline as the requests it delayed
            get_tracer().record_trace(
                "engine.reload_pause", pause,
                version=staged.weights_version, step=staged.step,
            )
        self.metrics.reloads.inc(label="ok")
        staged.outcome = "applied"
        try:
            from ..observability import get_flight_recorder

            get_flight_recorder().note(
                "weights_reload", step=staged.step,
                version=staged.weights_version, path=staged.path,
                generation=self.generation,
            )
        except Exception:
            pass

    def _on_weights_swapped(self):
        """Post-swap hook, called with the new weights installed and
        nothing in flight. The paged engine flushes its prefix cache
        here (and calls up); speculation re-snapshots the self-spec
        draft and invalidates old-weights draft caches."""
        if self.speculative is not None:
            self.speculative.on_weights_swapped(self)

    # ------------------------------------------------------- AOT warmup
    def _warmup_buckets(self):
        """Every prompt bucket this engine can compile (the same
        power-of-two ladder the pool admits)."""
        mx = getattr(self.pool, "max_seq_len", None) or self.max_seq_len
        out, L = [], getattr(self.pool, "min_bucket", 16)
        while True:
            b = self.pool.bucket_for(min(L, mx))
            if b not in out:
                out.append(b)
            if L >= mx:
                return out
            L *= 2

    def _decode_example_args(self):
        B = self.max_batch_size
        return (
            self._params, self._buffers, jnp.zeros((B,), jnp.int32),
            self._flat, *self._decode_extra(self._seqs),
            jnp.zeros((B,), jnp.int32),
            jnp.float32(self.temperature),
            jnp.zeros((B, 2), jnp.uint32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
        )

    def _adopt_example_args(self, flat_block, bucket):
        return (self._flat, flat_block, jnp.int32(0), self._no_feed,
                self._no_first)

    def _program_signature(self, name):
        cfg = self.config
        sig = {
            "program": name,
            "engine": type(self).__name__,
            "max_batch": self.max_batch_size,
            "max_seq": self.max_seq_len,
            "cache_dtype": str(self.cache_dtype),
            "do_sample": self.do_sample,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "model": {
                "vocab": int(cfg.vocab_size),
                "hidden": int(cfg.hidden_size),
                "inter": int(cfg.intermediate_size),
                "layers": int(cfg.num_hidden_layers),
                "heads": int(cfg.num_attention_heads),
                # what a cached token is made of, layer 0's arrays
                # (Llama: K and V of [kvH, D]; a latent net: one)
                "cache": [list(a) for a in cache_layout(cfg)[0]],
            },
        }
        if keeps_row_state(cfg):
            # what a row keeps beside its tokens, every layer's (a net
            # that keeps nothing a row has the key it always had)
            sig["model"]["cache_by_layer"] = [
                [list(a) for a in layer] for layer in cache_layout(cfg)]
            sig["model"]["rows"] = [
                [[list(shape), str(dtype)] for shape, dtype in layer]
                for layer in row_layout(cfg)]
        if name.startswith("spec_") and self.speculative is not None:
            # draft geometry/acceptance depth change the traced program
            # — a cache hit across different speculative configs would
            # install the wrong executable
            sig["speculative"] = self.speculative.signature()
        return sig

    def _verify_widths(self, buckets):
        """Block widths the speculative verify can see. The slab
        gathers every row at full width; the paged engine overrides
        with its bucket ladder."""
        return [self.max_seq_len]

    def _warm_spec_gather(self, cache, stats, buckets):
        """Pre-compile the KV-gather program(s) the speculative round
        issues before every verify. Slab: one full-width row gather."""
        self._warm_one(
            cache, "spec_gather", ("spec_gather",),
            self._spec_gather_prog(),
            (self._flat, jnp.int32(0)),
            lambda comp: setattr(self, "_spec_gather_fn", comp), stats,
        )

    def _warm_one(self, cache, name, trace_key, jitfn, args, install,
                  stats, donate=()):
        if trace_key in self._warmed:
            return  # idempotent: the installed executable stands
        stats["programs"] += 1
        key = meta = None
        if cache is not None:
            key, meta = cache.key_for(self._program_signature(name),
                                      args)
            comp = cache.load(key)
            if comp is not None:
                install(comp)
                self._warmed.add(trace_key)
                self.compile_cache_hits += 1
                stats["aot_hits"] += 1
                self._memory_note(name, jitfn, args, donate, comp)
                return
        comp = jitfn.lower(*args).compile()
        install(comp)
        self._warmed.add(trace_key)
        if cache is not None and cache.save(key, comp, meta):
            stats["aot_saves"] += 1
        self._memory_note(name, jitfn, args, donate, comp)

    def _memory_note(self, name, fn, args, donate, comp):
        """Record one warmed program's HBM footprint: the live-range
        estimate (memory_lint, with THIS process's actual donation) and
        the compiled executable's own ``memory_analysis()`` where the
        backend exposes it, drift already judged. Analysis can never
        fail a warmup."""
        try:
            from .. import analysis

            est = analysis.estimate_fn(
                fn, *args, graph=name, donate_argnums=donate,
            )
            entry = est.to_dict()
            stats = analysis.xla_memory_stats(comp)
            if stats is not None:
                entry["xla"] = stats
                drift = analysis.drift_finding(est, stats)
                entry["drift"] = None if drift is None else drift.message
            self.program_memory[name] = entry
        except Exception:
            pass

    def memory_report(self):
        """The per-program footprint table warmup() filled — the
        /healthz ``memory`` block and serve_bench's ``memory``
        record. None before warmup."""
        if not self.program_memory:
            return None
        return {
            "programs": dict(self.program_memory),
            "max_peak_bytes": max(
                e["peak_bytes"] for e in self.program_memory.values()
            ),
        }

    def _publish_memory_gauges(self):
        try:
            from ..observability import get_registry

            g = get_registry().gauge(
                "paddle_serving_program_peak_bytes",
                help="estimated peak resident bytes per compiled "
                     "serving program (memory_lint live-range model)",
                unit="bytes",
            )
            for name, entry in self.program_memory.items():
                g.set(float(entry["peak_bytes"]), program=name)
        except Exception:
            pass

    def warmup(self, aot_cache=None, buckets=None):
        """Compile every fixed-shape program — the decode step plus
        prefill and adopt per prompt bucket — BEFORE first traffic, so
        a fresh replica reaches READY with its full compiled inventory
        and the first request pays sockets, not XLA.

        With ``aot_cache`` (an ``jit.aot_cache.AOTProgramCache`` or a
        directory path), finished executables are serialized there and
        a relaunched engine with the same geometry loads them instead
        of tracing or compiling ANYTHING — ``compile_cache_hits``
        counts the loads, and the trace-guard inventory stays flat at
        first traffic (the reload-smoke acceptance pin). Returns
        ``{"programs", "aot_hits", "aot_saves"}``."""
        if self._closed:
            raise RuntimeError(f"{type(self).__name__} is closed")
        from ..jit import aot_cache as aot_mod

        cache = aot_mod.resolve(aot_cache)
        if buckets is None:
            buckets = self._warmup_buckets()
        stats = {"programs": 0, "aot_hits": 0, "aot_saves": 0}
        try:
            decode_fresh = ("decode",) not in self._warmed
            self._warm_one(
                cache, "decode", ("decode",), self._decode_fn,
                self._decode_example_args(),
                lambda comp: setattr(self, "_decode_fn", comp), stats,
                donate=(3,),
            )
            if decode_fresh:
                self.trace_guard.record_compile(
                    "serving::decode_step", "warmup", origin="warmup"
                )
            for b in buckets:
                blk = self.pool.alloc(b)
                try:
                    flat = _flatten(blk.caches)
                    pargs = (
                        self._params, self._buffers,
                        jnp.zeros((1, b), jnp.int32), jnp.int32(b),
                        flat, jnp.float32(self.temperature), self._key,
                    )
                    self._warm_one(
                        cache, f"prefill_b{b}", ("prefill", b),
                        self._prefill_fn(b), pargs,
                        lambda comp, b=b: self._prefill_fns
                        .__setitem__(b, comp), stats,
                        donate=(4,),
                    )
                    self._warm_one(
                        cache, f"adopt_b{b}", ("adopt", b),
                        self._adopt_fn(b),
                        self._adopt_example_args(flat, b),
                        lambda comp, b=b: self._adopt_fns
                        .__setitem__(b, comp), stats,
                        donate=(0,),
                    )
                finally:
                    self.pool.free(blk)
            if self.speculative is not None:
                # PR 16 residual: the speculative inventory (draft
                # prefill/decode, steady-state verify, gather) warms
                # and AOT-persists with everything else — the first
                # speculative round pays zero compiles
                self.speculative.warmup(self, cache, stats, buckets)
        finally:
            # lowering traces the program bodies — skipping the
            # restore leaks tracers into any LATER snapshot of the net
            self._restore_net_state()
        self._publish_memory_gauges()
        return stats

    def close(self):
        """Shut the engine down: cancel queued AND in-flight requests
        (their handles finish with status CANCELLED, partial tokens
        kept), release every slab slot so pool occupancy returns to 0,
        and drop all compiled programs."""
        self._closed = True
        if self._pending_swap is not None:
            self._pending_swap = None
            self.reload_in_progress = False
            self.metrics.reloads.inc(label="abandoned")
        while True:
            h = self.scheduler.pop_next()
            if h is None:
                break
            h.status = CANCELLED
            h.reason = REASON_ENGINE_CLOSED
            h.finish_time = self.clock()
            h._fire_terminal()
        for _ in self.scheduler.drain_timed_out():
            self.metrics.timeouts.inc()
        for i, seq in enumerate(self._seqs):
            if seq is None:
                continue
            h = seq.handle
            h.status = CANCELLED
            h.reason = REASON_ENGINE_CLOSED
            h.finish_time = self.clock()
            h.finished_step = self.step_count
            self._seqs[i] = None
            self._release_slot(i)
            h._fire_terminal()
        self._in_flight = None
        self._flat = None
        self._decode_fn = None
        if self.sessions is not None:
            self.sessions.close()
        # the guard's watch entry holds the jitted callable too — drop
        # it, or close() would keep the compiled program resident
        self.trace_guard.unwatch("serving::decode_step")
        self._prefill_fns.clear()
        self._adopt_fns.clear()
        self._spec_gather_fn = None
        if self.speculative is not None:
            self.speculative.unbind()


class StaticBatchEngine:
    """Serving adapter for SAVED decode artifacts (``jit.save`` ->
    ``inference.create_predictor``). A saved program is one fixed
    [B, S_prompt] whole-decode computation, so continuous batching is
    impossible — but the request/scheduler/metrics surface still
    applies: requests queue with backpressure, run in batches of B
    (short batches padded by repeating the first row), and report the
    same metrics. Built by ``Predictor.into_engine()``."""

    def __init__(self, predictor, *, max_queue_size=64, scheduler=None,
                 metrics=None, clock=time.monotonic, paged=False,
                 page_size=16):
        specs = getattr(predictor, "_input_specs", None)
        if not specs:
            raise ValueError(
                "predictor carries no input specs; into_engine() needs "
                "an artifact saved by paddle_tpu.jit.save"
            )
        shape = specs[0].get("shape") or []
        if len(shape) != 2:
            raise ValueError(
                f"expected a [B, S_prompt] decode artifact, got input "
                f"shape {shape}"
            )
        self.predictor = predictor
        self.batch_size, self.prompt_len = int(shape[0]), int(shape[1])
        self.clock = clock
        self.scheduler = scheduler or Scheduler(
            max_queue_size=max_queue_size, clock=clock
        )
        self.metrics = metrics or ServingMetrics()
        # paged residency accounting: the saved program's internal KV
        # span ([B, S_total]) flows through the same page-pool surface
        # the live paged engine uses (claim while a batch is in flight,
        # zero-leak when idle). The pool is sized on the first run — the
        # artifact only reveals S_total through its output shape.
        self._paged = bool(paged)
        self._page_size = int(page_size)
        self.page_pool = None
        self._total_len = None

    def submit(self, input_ids, *, priority=0, deadline_s=None,
               slo_class=None, on_token=None, on_event=None):
        req = Request(input_ids, 1, priority=priority,
                      deadline_s=deadline_s, slo_class=slo_class)
        self.metrics.submitted.inc()
        if req.prompt_len != self.prompt_len:
            h = RequestHandle(req, on_token=on_token, on_event=on_event)
            h.submit_time = h.finish_time = self.clock()
            h.status = REJECTED
            h.reason = REASON_SHAPE_MISMATCH
            self.metrics.rejected.inc(label=REASON_SHAPE_MISMATCH)
            h._fire_terminal()
            return h
        try:
            return self.scheduler.submit(req, on_token=on_token,
                                         on_event=on_event)
        except RejectedError as e:
            self.metrics.rejected.inc(label=e.reason)
            return e.handle

    def run_until_idle(self):
        name = self.predictor.get_input_names()[0]
        while self.scheduler.depth:
            self.scheduler.sweep_expired()
            for _ in self.scheduler.drain_timed_out():
                self.metrics.timeouts.inc()
            batch = []
            while len(batch) < self.batch_size:
                h = self.scheduler.pop_next()
                if h is None:
                    break
                batch.append(h)
            if not batch:
                continue
            ids = np.stack(
                [batch[i % len(batch)].request.input_ids
                 for i in range(self.batch_size)]
            ).astype(np.int32)
            t0 = self.clock()
            claim = None
            if self._paged and self.page_pool is not None:
                claim = self.page_pool.claim(
                    self.batch_size
                    * self.page_pool.pages_for(self._total_len)
                )
            self.predictor.get_input_handle(name).copy_from_cpu(ids)
            try:
                self.predictor.run()
                out = self.predictor.get_output_handle(
                    self.predictor.get_output_names()[0]
                ).copy_to_cpu()
            finally:
                if claim is not None:
                    self.page_pool.release(claim)
            dt = self.clock() - t0
            now = self.clock()
            new = out.shape[1] - self.prompt_len
            if self._paged and self.page_pool is None:
                # first run revealed S_total: size the pool to the
                # artifact's exact KV span and account this run's claim
                # retroactively (claims/releases counters still tally)
                from .paged_pool import PagedKVPool

                self._total_len = int(out.shape[1])
                pool = PagedKVPool(
                    None, page_size=self._page_size,
                    num_pages=self.batch_size
                    * -(-self._total_len // self._page_size),
                    max_seq_len=self._total_len,
                )
                pool.release(pool.claim(pool.num_pages))
                self.page_pool = pool
            for i, h in enumerate(batch):
                h.tokens = [int(t) for t in out[i, self.prompt_len:]]
                h.status = DONE
                h.admit_time = t0
                h.first_token_time = now
                h.finish_time = now
                self.metrics.admitted.inc()
                self.metrics.completed.inc()
                self.metrics.tokens_out.inc(new)
                self.metrics.prefill_tokens.inc(self.prompt_len)
                self.metrics.queue_wait.observe(t0 - h.submit_time)
                slo_ttft, slo_itl, slo_e2e = self.metrics.slo_children(
                    h.request.slo_class
                )
                slo_ttft.observe(now - h.submit_time)
                if new > 1:
                    slo_itl.observe(dt / new)
                slo_e2e.observe(now - h.submit_time)
                for t in h.tokens:
                    h._fire_token(t)
                h._fire_terminal()
            self.metrics.observe_step(self.scheduler.depth, len(batch))
        for _ in self.scheduler.drain_timed_out():
            self.metrics.timeouts.inc()
