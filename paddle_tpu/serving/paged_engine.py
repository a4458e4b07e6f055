"""Paged continuous-batching engine — resident HBM as the unit of win.

``ServingEngine``'s decode slab reserves a full ``[S_max]`` row per
request, so short requests waste most of their residency and the
concurrency ceiling is ``HBM / (S_max * token_bytes)`` regardless of
actual lengths. This engine keeps K/V in a PAGE ARENA
(:class:`~.paged_pool.PagedKVPool`) and each request claims only
``ceil(total_tokens / page_size)`` pages — at equal KV HBM, a
mixed-length workload admits strictly more concurrent requests (the
tier-1 test pins it against the slab engine, same budget, same
workload).

Compiled-program inventory (all fixed-shape, admission/retirement never
recompiles — the slab engine's core discipline carries over):

- **prefill** (per power-of-two prompt bucket): unchanged — the shared
  per-bucket programs from the base engine run the padded prompt
  through a transient block from the bucketed block pool.
- **adopt-pages** (per bucket): scatters the prefilled ``[1, bucket]``
  block into the arena as ``bucket / page_size`` whole pages at
  table-supplied ids (tail ids past the request's claim point at the
  garbage page 0 — no shape variance, no recompiles), and writes the
  prefill's first token into the decode row's place of the tokens the
  next decode step is fed from the device (the base engine's
  ``_seat``). Over a net that keeps a state a ROW beside its pages
  (``generation.row_layout``: a recurrent layer) the block also
  carries the prompt's final state, and the program,
  ``adopt_state_body``, copies it into the decode row: two kinds of
  cache in one manager, arrays addressed by token through the page
  table and arrays addressed by row.
- **decode step** (exactly one): ``[B]`` tokens + the ``[B, P_max]``
  page table -> next tokens; attention gathers K/V through the table,
  and only as far as the batch's longest row reaches: the program
  picks, on the device from ``pos``, the narrowest rung of a ladder of
  table widths (eighths of ``P_max``) that holds every row, and one
  ``lax.switch`` runs the gather and the contraction at that width
  (``quantization.kv.write_and_attend_paged``). One program serves
  every span; ``metrics.span_tokens`` records the width a launch.
- **gather-pages** (per bucket, prefix-cache mode): materializes a
  request's cached-prefix pages as a prefill-layout block so the tail
  program can attend over them.
- **chunk-prefill** (per (bucket, tail-bucket) pair, prefix-cache
  mode): runs ONLY the uncached tail of a prompt at a traced position
  offset — the warm path's near-zero prefill compute.

Prefill/decode disaggregation: prefill and decode are separate
compiled units, and ``max_prefills_per_step`` (default 1) bounds how
many prompt prefills one engine step may run before the decode step
fires — a burst of long prompts delays in-flight decodes by at most one
bucket's prefill per step instead of stalling them behind the whole
backlog. Prefilled requests enter the decode batch purely by having
their pages written and their table row set.

PREFIX CACHING (``prefix_cache=True`` / a ``PrefixCache``): prefill
pages are published under ``(weights_version, cache_dtype,
token-prefix hash chain)`` keys at page granularity with refcounts; a
new request adopts every matching full page BY REFERENCE into its page
table, prefill runs only on the uncached tail, a recompute boundary
inside a shared page copy-on-write clones it through the gather ->
chunk -> adopt pipeline, cold refcount-zero prefixes are LRU-evicted
under arena pressure, and a weight reload flushes the store. Prefix
mode also switches decode pages to DEMAND GROWTH (``demand_paging=``
to control it independently): admission claims only the prompt's
pages, each decode step claims the next page as a row crosses a page
boundary, and a growth failure sheds THAT request with reason
``pages_exhausted`` — never a crash, never another row's pages.

Token streams are exact-equal to ``net.generate`` and the slab engine
— including warm prefix hits: adopted KV is prefill-provenance content
for the identical token prefix under identical weights, and the
chunked tail program is pinned bitwise-equal to the full prefill.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiler
from ..models.generation import (
    _select_next,
    decode_step,
    row_array_mask,
)
from ..observability.tracing import get_tracer
from ..quantization import kv as qkv
from .engine import (
    ServingEngine,
    _RequestPhase,
    _flatten,
    _unflatten,
    build_chunk_prefill_body,
    step_counters,
)
from .paged_pool import PagedKVPool, PagesExhausted
from .scheduler import CANCELLED, REASON_PAGES_EXHAUSTED


class PagedServingEngine(ServingEngine):
    """Continuous batching over a paged KV pool.

    Same request surface as :class:`ServingEngine` (submit / step /
    run_until_idle / generate / close, streaming callbacks, scheduler,
    metrics). Geometry: ``page_size`` must be a power of two that
    divides ``min_bucket`` AND ``max_seq_len`` (adoption scatters whole
    pages; the top prompt bucket is capped at ``max_seq_len``).
    ``num_pages`` (usable pages, garbage page excluded) defaults to
    full-coverage ``max_batch_size * ceil(max_seq_len / page_size)`` —
    pass a smaller arena to trade concurrency headroom for HBM, the
    whole point of paging.

    ``prefix_cache=True`` (or a :class:`~.prefix_cache.PrefixCache`
    over the same pool) enables copy-on-write prefix page sharing;
    ``demand_paging`` defaults to the prefix-cache setting and grows
    decode pages per step instead of claiming them up front."""

    def __init__(self, net, *, max_batch_size=8, max_seq_len=256,
                 page_size=16, num_pages=None, cache_dtype=None,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 seed=0, min_bucket=16, max_queue_size=64,
                 max_tokens_in_flight=None, max_prefills_per_step=1,
                 scheduler=None, metrics=None, pool=None, page_pool=None,
                 clock=time.monotonic, recompile_guard_max=None,
                 weights_version=None, prefill_transport=None,
                 reload_template=None, prefix_cache=None,
                 demand_paging=None, speculative=None,
                 kv_tiering=None, sessions=None):
        ps = int(page_size)
        if ps < 1 or (ps & (ps - 1)):
            raise ValueError(
                f"page_size must be a power of two, got {page_size}"
            )
        if ps > int(min_bucket) or int(min_bucket) % ps:
            raise ValueError(
                f"page_size {ps} must divide every prefill bucket: "
                f"min_bucket {min_bucket} must be a multiple of it"
            )
        if int(max_seq_len) % ps:
            raise ValueError(
                f"max_seq_len {max_seq_len} must be a multiple of "
                f"page_size {ps} (the top prompt bucket is capped at "
                f"max_seq_len and adoption scatters whole pages)"
            )
        self.page_size = ps
        self._num_pages_arg = num_pages
        self._page_pool_arg = page_pool
        self._prefix_cache_arg = prefix_cache
        # hierarchical KV tiering (kv_tiering.TieredPageStore): True
        # builds a default host-RAM tier, a dict passes ctor kwargs
        # through, a built store attaches as-is. Requires a prefix
        # cache — the tier spills/restores ITS pages.
        self._kv_tiering_arg = kv_tiering
        if kv_tiering not in (None, False) \
                and prefix_cache in (None, False):
            raise ValueError(
                "kv_tiering requires prefix_cache: the tier spills "
                "and restores prefix-cache pages"
            )
        self._demand_paging = (
            bool(demand_paging) if demand_paging is not None
            else prefix_cache not in (None, False)
        )
        self.max_prefills_per_step = (
            None if max_prefills_per_step is None
            else int(max_prefills_per_step)
        )
        # cross-process disaggregation: when a transport (a
        # fleet.kv_transfer.RemotePrefillClient) is attached, admission
        # ships the prompt to the prefill pool and adopts the returned
        # KV pages; any transfer failure falls back to LOCAL prefill on
        # this engine — disaggregation is an optimization, never a
        # correctness dependency. A prefix-cache hit skips the
        # transport entirely (the tail chunk is cheaper than the wire).
        self.prefill_transport = prefill_transport
        self.remote_prefills = 0
        self.local_prefills = 0
        self.chunk_prefills = 0
        self.remote_prefill_fallbacks = 0
        super().__init__(
            net, max_batch_size=max_batch_size, max_seq_len=max_seq_len,
            cache_dtype=cache_dtype, do_sample=do_sample,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            min_bucket=min_bucket, max_queue_size=max_queue_size,
            max_tokens_in_flight=max_tokens_in_flight,
            scheduler=scheduler, metrics=metrics, pool=pool, clock=clock,
            recompile_guard_max=recompile_guard_max,
            weights_version=weights_version,
            reload_template=reload_template,
            speculative=speculative, sessions=sessions,
        )
        if self.prefix_cache is not None and recompile_guard_max is None:
            # prefix mode legitimately compiles one gather program per
            # bucket and one chunk program per (bucket, tail-bucket)
            # pair — widen the storm bar to the real steady-state
            # inventory instead of firing on warm-path compiles. A
            # spill tier adds ONE more: the page-size restore adopt.
            nb = len(self._warmup_buckets())
            self.trace_guard.max_compiles = max(
                self.trace_guard.max_compiles,
                nb * (nb + 3) // 2 + 2
                + (1 if self.kv_tier is not None else 0),
            )

    # ------------------------------------------------------- KV backend
    def _kv_pair_features(self, speculative):
        out = super()._kv_pair_features(speculative)
        out["the prefix cache"] = \
            self._prefix_cache_arg not in (None, False)
        out["KV tiering"] = self._kv_tiering_arg not in (None, False)
        out["remote prefill"] = self.prefill_transport is not None
        return out

    def _init_kv_backend(self):
        num_pages = self._num_pages_arg
        if num_pages is None:
            num_pages = (self.max_batch_size
                         * (-(-self.max_seq_len // self.page_size)))
        pp = self._page_pool_arg or PagedKVPool(
            self.config, page_size=self.page_size, num_pages=num_pages,
            dtype=self.cache_dtype, max_seq_len=self.max_seq_len,
        )
        if pp.page_size != self.page_size:
            raise ValueError(
                f"page_pool page_size {pp.page_size} != engine "
                f"page_size {self.page_size}"
            )
        if jnp.dtype(pp.dtype) != jnp.dtype(self.cache_dtype):
            raise ValueError(
                f"page_pool dtype {pp.dtype} != prefill block dtype "
                f"{self.cache_dtype} — adoption would silently cast"
            )
        if pp.table_width() * pp.page_size < self.max_seq_len:
            raise ValueError(
                f"page_pool table width {pp.table_width()} covers only "
                f"{pp.table_width() * pp.page_size} tokens < engine "
                f"max_seq_len {self.max_seq_len}"
            )
        self.page_pool = pp
        pc = self._prefix_cache_arg
        if pc is True:
            from .prefix_cache import PrefixCache

            pc = PrefixCache(pp)
        elif pc in (None, False):
            pc = None
        elif pc.pool is not pp:
            raise ValueError(
                "prefix_cache wraps a different PagedKVPool than this "
                "engine's — pass the same pool to both"
            )
        self.prefix_cache = pc
        tier = getattr(self, "_kv_tiering_arg", None)
        if tier is True:
            from .kv_tiering import TieredPageStore

            tier = TieredPageStore()
        elif isinstance(tier, dict):
            from .kv_tiering import TieredPageStore

            tier = TieredPageStore(**tier)
        elif tier in (None, False):
            tier = None
        self.kv_tier = tier
        if tier is not None:
            pc.attach_tier(
                tier,
                read_page=self._tier_read_page,
                restore_page=self._tier_restore_page,
                current_version=lambda: self.weights_version,
            )
        self.table_width = pp.table_width()
        # a net that keeps a state a row (generation.row_layout) gets
        # it here, a row of each array a decode row, beside the pages
        self._flat = _flatten(pp.alloc_arena_arrays(
            rows=self.max_batch_size))
        self._row_arrays = row_array_mask(self.config)
        self._tables = np.zeros(
            (self.max_batch_size, self.table_width), np.int32
        )
        self._row_pages = [None] * self.max_batch_size
        self._row_meta = [None] * self.max_batch_size
        self._free_rows = list(range(self.max_batch_size))[::-1]
        self._gather_fns = {}   # bucket -> jitted fn
        self._chunk_fns = {}    # (bucket, tail_bucket) -> jitted fn
        # speculative-verify page accounting (the zero-leak pin reads
        # these: every transient verify page claimed must either stay
        # owned by the accepting request or come back on rollback)
        self.spec_pages_claimed = 0
        self.spec_pages_rolled_back = 0

    def _release_slot(self, slot):
        if self.speculative is not None:
            self.speculative.reset_slot(slot)
        pages = self._row_pages[slot]
        meta = self._row_meta[slot]
        if (pages and meta is not None and self.prefix_cache is not None
                and not self._closed):
            # publish-on-finish: the partial prompt-tail page becomes
            # shareable the moment its owner stops writing it (a later
            # same-prefix request COW-adopts it instead of re-running
            # the tail) — prefill-valid slots only, decode KV never
            prompt, prompt_len = meta
            r = prompt_len % self.page_size
            k = prompt_len // self.page_size
            if r and k < len(pages):
                self.prefix_cache.publish_partial(
                    prompt, prompt_len, pages[k], self.weights_version
                )
        if pages:
            self.page_pool.release(pages)
        if self.prefix_cache is not None:
            self.prefix_cache.update_gauges()
        self._row_pages[slot] = None
        self._row_meta[slot] = None
        self._tables[slot, :] = 0  # free row reads/writes garbage page
        # what the net keeps a ROW (a recurrent state) is not cleared:
        # a free row's garbage updates stay in the free row, and the
        # next adoption overwrites state and tail whole
        self._free_rows.append(slot)

    def _finish(self, slot, status, reason=None):
        """Decode-publish, then the base terminal transition. While
        the row's sequence and pages are still live, every page the
        finished request WROTE — prompt AND generated answer — is
        published into the prefix chain: the decode step and the
        prefill program share one masked-SDPA op order (pinned
        bitwise-equal in tier-1, bf16 and int8), so decode-written KV
        for position ``p`` is byte-for-byte what re-prefilling
        ``tokens[0..p]`` would write. Valid span: the LAST emitted
        token's KV is never written (nothing consumed it), so
        ``prompt_len + emitted - 1`` positions publish — turn N+1 of
        a chat warm-admits turn N's full context including the
        answer."""
        seq = self._seqs[slot]
        if (seq is not None and self.prefix_cache is not None
                and not self._closed):
            pages = self._row_pages[slot]
            meta = self._row_meta[slot]
            if pages and meta is not None:
                h = seq.handle
                prompt, prompt_len = meta
                toks = prompt + tuple(int(t) for t in h.tokens)
                valid = prompt_len + max(0, len(h.tokens) - 1)
                if valid > prompt_len:
                    self.prefix_cache.publish(
                        toks, valid, pages, self.weights_version
                    )
                    ps = self.page_size
                    k, r = valid // ps, valid % ps
                    if r and k < len(pages):
                        self.prefix_cache.publish_partial(
                            toks, valid, pages[k], self.weights_version
                        )
        super()._finish(slot, status, reason=reason)

    @property
    def free_rows(self):
        return len(self._free_rows)

    def _has_capacity(self):
        return bool(self._free_rows)

    def _too_long(self, req):
        # a request needing more pages than the whole arena would sit
        # at the head of the strict-FIFO queue forever, blocking every
        # later request — reject it at submit instead
        return (super()._too_long(req)
                or self.page_pool.pages_for(req.total_tokens)
                > self.page_pool.num_pages)

    def _pages_at_admission(self, prompt_len, total_tokens):
        """Pages a request's table needs when admitted: the whole span
        up front classically; only the prompt's pages under demand
        growth (decode pages are claimed per step as rows cross page
        boundaries)."""
        return self.page_pool.pages_for(
            prompt_len if self._demand_paging else total_tokens
        )

    def _admission_budget(self):
        """Head must fit BOTH the in-flight token cap and the free
        pages. ``total <= free_pages * page_size`` is exactly
        ``ceil(total / page_size) <= free_pages``, so the token-budget
        gate doubles as the page gate — strict FIFO is preserved (a big
        head waits, nothing overtakes it). In prefix/demand mode the
        page side moves to :meth:`_admission_fits` (a warm request's
        real need depends on cache coverage, which a scalar budget
        cannot express)."""
        base = ServingEngine._admission_budget(self)
        if self._demand_paging or self.prefix_cache is not None:
            return base
        page_budget = self.page_pool.free_pages * self.page_size
        return page_budget if base is None else min(base, page_budget)

    def _admission_fits(self):
        if self.prefix_cache is None and not self._demand_paging:
            return None

        def fits(req):
            n_init = self._pages_at_admission(req.prompt_len,
                                              req.total_tokens)
            n_ref = 0
            ref_pages = ()
            match, plan = self._prefix_probe(req)
            if plan is not None:
                n_ref = plan[0] // self.page_size
                ref_pages = match.pages[:n_ref]
            need = n_init - n_ref
            if need <= self.page_pool.free_pages:
                return True  # freelist covers it — skip the cache walk
            if self.prefix_cache is None:
                return False
            # the pages this request would ADOPT are excluded: eviction
            # can never reclaim what admission is about to reference —
            # counting them would pass a head whose claim then fails
            return need <= (self.page_pool.free_pages
                            + self.prefix_cache.evictable_pages(
                                exclude=ref_pages))

        return fits

    def _prefix_probe(self, req):
        """One chain walk + chunk plan per request per admission
        attempt, shared between the fits predicate and ``_admit_one``
        (same driver thread, nothing mutates the cache between the pop
        check and the admission that immediately follows it). The
        result is stashed on the request and consumed by admission;
        a head that waits re-probes on its next pop attempt."""
        if self.prefix_cache is None:
            return None, None
        m = self.prefix_cache.match(req.input_ids, req.prompt_len,
                                    self.weights_version)
        plan = None
        if m.covered > 0:
            bucket = self.pool.bucket_for(req.prompt_len)
            plan = self._chunk_plan(req.prompt_len, bucket, m.covered)
        out = (m if plan is not None else None, plan)
        req.__dict__["_prefix_probe_result"] = out
        return out

    def _max_admissions_per_step(self):
        return self.max_prefills_per_step

    # ------------------------------------------------- compiled programs
    def _decode_body(self, params, buffers, tok, flat, tbl, pos,
                     temperature, key, prev, from_host):
        # a later trace puts the weights back too (base engine)
        self._traced.discard(("decode",))
        self.net.load_functional_state(params, buffers)
        self.net.eval()
        # the device's own token for a continuing row (base engine)
        tok = jnp.where(from_host, tok, prev)
        logits, caches = decode_step(
            self.net, tok[:, None], _unflatten(flat, self.config), pos,
            page_table=tbl,
        )
        if self.do_sample:
            # per-row position-addressed keys (see the base engine)
            key = jax.vmap(jax.random.fold_in)(key, pos + 1)
        nxt = _select_next(logits, self.do_sample, temperature,
                           self.top_k, self.top_p, key)
        return nxt, _flatten(caches), step_counters(self.net)

    def _decode_extra(self, fed):
        # an active row the launch feeds nothing writes its garbage
        # where a free row does, into page 0, not into its own pages
        # (they may be shared, and are published when it finishes)
        unfed = [i for i, seq in enumerate(self._seqs)
                 if seq is not None and fed[i] is None]
        tables = self._tables
        if unfed:
            tables = tables.copy()
            tables[unfed] = 0
        return (jnp.asarray(tables),)

    def _read_span(self, pos):
        # what the decode program picks on the device from the same pos
        ladder = qkv.span_ladder(self.table_width)
        return self.page_size * ladder[
            qkv.span_rung(self.table_width, pos, self.page_size)]

    def _adopt_fn(self, bucket):
        """Scatter a prefilled [1, bucket] block into the arena as
        ``bucket / page_size`` whole pages at traced page ids — one
        program per bucket, ids beyond the request's claim point at the
        garbage page 0 (duplicate scatter indices there are fine: the
        page is garbage by contract), and the prefill's ``first`` token
        into decode ``row``'s place of ``feed`` (the base engine's
        ``_adopt_fn``). Over a net that keeps a state a row the program
        (``adopt_state_body``) also copies the block's row arrays, the
        prompt's final state, into that row. Returns the arena's arrays
        and, last, the feed."""
        fn = self._adopt_fns.get(bucket)
        if fn is not None:
            return fn
        ps = self.page_size
        n_pages_b = bucket // ps
        by_row = self._row_arrays

        def adopt_body(flat_arena, flat_block, page_ids, row, feed, first):
            from ..quantization.kv import adopt_into_pages, adopt_into_slab

            return [
                adopt_into_slab(a, b, row) if is_row
                else adopt_into_pages(a, b, page_ids, n_pages_b, ps)
                for a, b, is_row in zip(flat_arena, flat_block, by_row)
            ] + [jax.lax.dynamic_update_slice(feed, first, (row,))]

        if any(by_row):
            adopt_body.__name__ = adopt_body.__qualname__ = \
                "adopt_state_body"
        fn = jax.jit(
            adopt_body, donate_argnums=(0,)
        )
        self._adopt_fns[bucket] = fn
        self.trace_guard.record_compile(
            "serving::adopt_pages", bucket,
            origin="serving/paged_engine.py",
        )
        return fn

    def _gather_fn(self, bucket):
        """Materialize ``bucket / page_size`` arena pages at traced ids
        as one prefill-layout block — the warm path's cached-prefix
        context (ids past the cached span -> garbage page 0, whose
        content sits behind the position mask like any stale slot). The
        arena is NOT donated: shared pages must survive the gather."""
        fn = self._gather_fns.get(bucket)
        if fn is not None:
            return fn
        ps = self.page_size
        n_pages_b = bucket // ps

        def gather_body(flat_arena, src_ids):
            from ..quantization.kv import gather_block_from_pages

            return [
                gather_block_from_pages(a, src_ids, n_pages_b, ps)
                for a in flat_arena
            ]

        fn = jax.jit(gather_body)
        self._gather_fns[bucket] = fn
        self.trace_guard.record_compile(
            "serving::gather_pages", bucket,
            origin="serving/paged_engine.py",
        )
        return fn

    def _chunk_fn(self, bucket, tail_bucket):
        """The chunked-prefill program: tail tokens [1, tail_bucket] at
        a traced position offset over a gathered [1, bucket] block —
        one program per (bucket, tail-bucket) pair, O(log^2) total."""
        fn = self._chunk_fns.get((bucket, tail_bucket))
        if fn is not None:
            return fn
        body = build_chunk_prefill_body(self.net, self.do_sample,
                                        self.top_k, self.top_p)
        fn = jax.jit(
            body, donate_argnums=(5,)
        )
        self._chunk_fns[(bucket, tail_bucket)] = fn
        self.trace_guard.record_compile(
            "serving::chunk_prefill", (bucket, tail_bucket),
            origin="serving/paged_engine.py",
        )
        return fn

    def _adopt_example_args(self, flat_block, bucket):
        return (
            self._flat, flat_block,
            jnp.zeros((bucket // self.page_size,), jnp.int32),
            jnp.int32(0), self._no_feed, self._no_first,
        )

    def _program_signature(self, name):
        sig = super()._program_signature(name)
        sig["page_size"] = self.page_size
        sig["num_pages"] = self.page_pool.num_pages
        sig["table_width"] = self.table_width
        return sig

    # --------------------------------------------------- prefix caching
    def _tail_buckets(self, bucket):
        """The tail-chunk shape ladder for one prompt bucket: the
        power-of-two prefill ladder capped at the bucket itself."""
        out, L = [], int(getattr(self.pool, "min_bucket", 16))
        while L < bucket:
            out.append(L)
            L *= 2
        out.append(bucket)
        return out

    def _chunk_plan(self, prompt_len, bucket, covered):
        """Pick the warm path's (recompute start ``c``, tail bucket):
        maximize the cached span actually reused, under the hard shape
        constraint ``c + tail_bucket <= bucket`` (the chunk writes
        [c, c + tail_bucket) into the block — clamped dynamic slices
        would silently corrupt positions otherwise) and ``c <=
        prompt_len - 1`` (the last prompt token is always re-run: its
        logits produce the first output token). None when no plan
        reuses anything (degenerate -> cold path)."""
        best = None
        for tb in self._tail_buckets(bucket):
            c = min(int(covered), prompt_len - 1, bucket - tb)
            if c <= 0 or prompt_len - c > tb:
                continue
            if best is None or c > best[0]:
                best = (c, tb)
        return best

    def _claim_pages(self, n):
        """Fresh pages, evicting cold cached prefixes under pressure.
        Raises :class:`PagesExhausted` only when the freelist AND the
        reclaimable side of the cache together cannot cover ``n``."""
        try:
            return self.page_pool.claim(n)
        except PagesExhausted:
            if self.prefix_cache is None:
                raise
            need = n - self.page_pool.free_pages
            self.prefix_cache.evict(need)
            return self.page_pool.claim(n)

    # ------------------------------------------------------- KV tiering
    def _tier_read_page(self, page_id):
        """One arena page's bytes on the host, flattened one array per
        raw buffer (a QuantizedKV leaf contributes q then scale) — the
        spill side of the tier attachment. Read-only: shared pages are
        never touched, only copied out."""
        from ..quantization.kv import is_quantized

        out = []
        for leaf in self._flat:
            if is_quantized(leaf):
                out.append(np.asarray(leaf.q[page_id]))
                out.append(np.asarray(leaf.scale[page_id]))
            else:
                out.append(np.asarray(leaf[page_id]))
        return out

    def _page_block(self, arrays=None):
        """A [1, page_size]-wide flat block matching ``self._flat``'s
        leaf structure — from spilled host ``arrays`` (restore), or
        zeros (warmup example args). One shape for both, so the
        restore program warms with the exact block it later runs."""
        from ..quantization.kv import QuantizedKV, is_quantized

        ps = self.page_size
        block, i = [], 0
        for leaf in self._flat:
            if is_quantized(leaf):
                if arrays is None:
                    kvh, d = leaf.q.shape[2], leaf.q.shape[3]
                    q = jnp.zeros((1, ps, kvh, d), leaf.q.dtype)
                    s = jnp.zeros((1, ps, kvh), leaf.scale.dtype)
                else:
                    q = jnp.asarray(arrays[i])[None]
                    s = jnp.asarray(arrays[i + 1])[None]
                block.append(QuantizedKV(q, s))
                i += 2
            else:
                if arrays is None:
                    a = jnp.zeros((1, ps) + tuple(leaf.shape[2:]),
                                  leaf.dtype)
                else:
                    a = jnp.asarray(arrays[i])[None]
                block.append(a)
                i += 1
        return block

    def _tier_restore_page(self, arrays):
        """The restore side: claim one fresh arena page, adopt the
        spilled bytes into it through the page-size adopt program
        (same scatter the prefill path uses — restored bytes land
        bit-identical), return its id. None when the arena has no
        page to spare RIGHT NOW — the record stays spilled and the
        request cold-prefills; claiming directly from the pool (not
        ``_claim_pages``) keeps a restore from recursing into
        eviction, which could spill the very chain being walked."""
        try:
            page = self.page_pool.claim(1)
        except PagesExhausted:
            return None
        ps = self.page_size
        with profiler.RecordEvent("serving::restore_adopt", bucket=ps):
            self._adopt(ps, self._page_block(arrays),
                        jnp.asarray(page, jnp.int32), jnp.int32(0))
        return page[0]

    # ------------------------------------------- speculative backend seams
    def _verify_widths(self, buckets):
        """Paged verify blocks are bucketed gathers — one verify
        program per prompt bucket, not one full-width program."""
        return list(buckets)

    def _warm_spec_gather(self, cache, stats, buckets):
        """The speculative round's per-bucket gather — the SAME
        programs (and ``("gather", b)`` warm keys) the prefix-cache
        warm path compiles, so with a prefix cache attached this is an
        idempotent no-op pass."""
        ps = self.page_size
        for b in buckets:
            self._warm_one(
                cache, f"gather_b{b}", ("gather", b),
                self._gather_fn(b),
                (self._flat, jnp.zeros((b // ps,), jnp.int32)),
                lambda comp, b=b: self._gather_fns
                .__setitem__(b, comp), stats,
            )

    def _spec_reserve(self, slot, hi):
        """Demand-claim pages so row ``slot`` holds KV capacity through
        cache position ``hi`` (the verify writes [pos, hi]); appended
        to the row's OWNED pages and table like any demand growth, so
        occupancy gauges count them while held. Under page pressure the
        round clamps to what the pool can cover — worst case the
        request's current position, a one-token vanilla-equivalent
        verify — instead of shedding anybody."""
        hi = min(hi, self.max_seq_len - 1)
        pages = self._row_pages[slot]
        ps = self.page_size
        while hi // ps >= len(pages):
            try:
                new = self._claim_pages(1)
            except PagesExhausted:
                break
            self._tables[slot, len(pages)] = new[0]
            pages.append(new[0])
            self.spec_pages_claimed += 1
        return min(hi, len(pages) * ps - 1)

    def _spec_gather(self, slot, hi):
        """Row ``slot``'s owned pages as one prefill-layout block wide
        enough to cover position ``hi`` — the same bucketed gather
        program the prefix-cache warm path runs (pad ids -> garbage
        page 0, masked)."""
        ps = self.page_size
        bucket = self.pool.bucket_for(hi + 1)
        pages = self._row_pages[slot]
        src = np.zeros((bucket // ps,), np.int32)
        n = min(len(pages), bucket // ps)
        src[:n] = pages[:n]
        with profiler.RecordEvent("serving::spec_gather", bucket=bucket):
            flat_block = self._run(
                ("gather", bucket), self._gather_fn(bucket),
                self._flat, jnp.asarray(src),
            )
        return flat_block, bucket

    def _spec_adopt(self, slot, new_block, width, pos):
        """Scatter the verify-updated block back — ONLY the pages the
        verify may have written (index >= pos // page_size; all owned
        exclusively: pos >= prompt_len, and shared prefix pages end at
        the prompt's last full-page boundary). Everything below
        scatters to garbage page 0, so a shared page is never written
        even with identical content."""
        ps = self.page_size
        pages = self._row_pages[slot]
        page_ids = np.zeros((width // ps,), np.int32)
        lo = pos // ps
        n = min(len(pages), width // ps)
        page_ids[lo:n] = pages[lo:n]
        self._adopt(width, new_block, jnp.asarray(page_ids),
                    jnp.int32(slot))

    def _spec_rollback(self, slot, new_pos):
        """Release the rejected tail's demand-claimed pages (anything
        past the page holding ``new_pos``) back to the pool and zero
        their table entries — the zero-leak pin. Classic (non-demand)
        mode keeps the row's full up-front span untouched."""
        if not self._demand_paging:
            return
        pages = self._row_pages[slot]
        keep = new_pos // self.page_size + 1
        if len(pages) <= keep:
            return
        tail = pages[keep:]
        del pages[keep:]
        self._tables[slot, keep:keep + len(tail)] = 0
        self.page_pool.release(tail)
        self.spec_pages_rolled_back += len(tail)

    def _on_weights_swapped(self):
        # the reload-flush satellite: every cached page was computed
        # under the weights that just rotated out — a post-swap request
        # must miss (keys re-root on the new version too, belt and
        # braces). The swap only applies at a zero-in-flight boundary,
        # so the cache holds the only reference to every page and the
        # flush returns them all to the freelist.
        if self.prefix_cache is not None:
            self.prefix_cache.flush(reason="weights_reload")
        # up-call: speculation re-snapshots the self-spec draft and
        # invalidates old-weights draft caches
        super()._on_weights_swapped()

    # ---------------------------------------------------------- requests
    def _drop_block(self, blk):
        """Return a prefill block after a failed admission. The failed
        call may already have consumed the block's donated buffers —
        recycling would poison the freelist, so discard."""
        if blk is not None:
            self.pool.discard(blk)

    def _remote_prefill(self, req, bucket, key, trace=None):
        """Try the attached prefill pool: ``(first_token, flat_block)``
        (the token a host integer) on success, None when the transport
        is absent/down/failing (the caller runs local prefill — clean
        fallback, counted).
        ``trace`` is the admission's prefill span: the transport
        parents its wire span (and the worker's remote span) under
        it."""
        tr = self.prefill_transport
        if tr is None or not tr.available():
            return None
        from .fleet.kv_transfer import TransferError

        try:
            out = tr.prefill(
                [int(t) for t in req.input_ids], req.prompt_len, bucket,
                self.page_size, str(self.cache_dtype),
                float(self.temperature), key, trace=trace,
            )
        except TransferError:
            self.remote_prefill_fallbacks += 1
            return None
        self.remote_prefills += 1
        return out

    def _admit_one(self, handle):
        req = handle.request
        now = self.clock()
        ps = self.page_size
        bucket = self.pool.bucket_for(req.prompt_len)
        n_init = self._pages_at_admission(req.prompt_len,
                                          req.total_tokens)
        # sampling key drawn ONCE so a remote-prefill failure that falls
        # back locally consumes the same key the pure-local path would —
        # sampled streams stay reproducible either way (warm hits
        # consume it in the chunk program's sampling head)
        key = self._next_key()
        # prefix-cache walk: adopt matching full pages by reference and
        # recompute only the uncached tail. The fits predicate already
        # walked the chain for this pop — reuse its stashed probe
        # instead of matching twice per admission.
        match = plan = None
        if self.prefix_cache is not None:
            probe = req.__dict__.pop("_prefix_probe_result", None)
            if probe is None:
                probe = self._prefix_probe(req)
                req.__dict__.pop("_prefix_probe_result", None)
            match, plan = probe
            if match is not None:
                self.prefix_cache.hits.inc()
                self.prefix_cache.tokens_saved.inc(plan[0])
            else:
                self.prefix_cache.misses.inc()
        # the per-admission prefill span: mode (remote|local|fallback|
        # chunk) plus the prefix-hit/chunk-plan attributes the warm
        # path decided on — None (zero allocations) when sampled out.
        # It also covers the remote attempt and the gather, which no
        # phase does, so it is opened here and not by a _RequestPhase
        t_pre = self.clock()
        psp = None if handle.trace is None else get_tracer().start_span(
            "engine.prefill", handle.trace, bucket=bucket,
            prefix_hit=match is not None,
        )
        if psp is not None and plan is not None:
            psp.set(chunk_start=plan[0], tail_bucket=plan[1],
                    cached_tokens=plan[0])
        fb0 = self.remote_prefill_fallbacks
        remote = None
        blk = None
        if match is None:
            remote = self._remote_prefill(req, bucket, key, trace=psp)
            if remote is None:
                ids = np.zeros((1, bucket), np.int32)
                ids[0, : req.prompt_len] = req.input_ids
                blk = self.pool.alloc(req.prompt_len)
        if psp is not None:
            psp.set(mode=(
                "chunk" if match is not None
                else "remote" if remote is not None
                else "fallback" if self.remote_prefill_fallbacks > fb0
                else "local"
            ))
        n_ref = 0 if match is None else plan[0] // ps
        ref_pages = [] if match is None else match.pages[:n_ref]
        row = None
        owned = []
        try:
            if n_ref:
                # reference the shared pages BEFORE any claim: claiming
                # may evict, and eviction must see these as in-use
                self.page_pool.incref(ref_pages)
                owned.extend(ref_pages)
            fresh = self._claim_pages(n_init - n_ref)
            owned.extend(fresh)
            row = self._free_rows.pop()
            row_pages = ref_pages + fresh
            self._tables[row, :] = 0
            self._tables[row, :n_init] = row_pages
            if match is not None:
                c, tb = plan
                L = req.prompt_len - c
                n_gather = -(-c // ps)
                src = np.zeros((bucket // ps,), np.int32)
                src[:n_gather] = match.pages[:n_gather]
                with _RequestPhase("gather", handle, bucket=bucket,
                                   span={"pages": n_gather}, parent=psp):
                    flat_block = self._run(
                        ("gather", bucket), self._gather_fn(bucket),
                        self._flat, jnp.asarray(src),
                    )
                tail = np.zeros((1, tb), np.int32)
                tail[0, :L] = req.input_ids[c:]
                self.chunk_prefills += 1
                with _RequestPhase("chunk_prefill", handle, bucket=bucket,
                                   tail=tb):
                    first, new_flat = self._run(
                        ("chunk", bucket, tb),
                        self._chunk_fn(bucket, tb),
                        self._params, self._buffers, jnp.asarray(tail),
                        jnp.int32(L), jnp.int32(c), flat_block,
                        jnp.float32(self.temperature), key,
                    )
                if c % ps:
                    # recompute boundary inside a cached page: its
                    # content was cloned through the gather into a
                    # fresh page this request owns — the copy-on-write
                    # (the shared original is never written)
                    self.prefix_cache.cow_clones.inc()
            elif remote is None:
                self.local_prefills += 1
                with _RequestPhase("prefill", handle, bucket=bucket):
                    first, new_flat = self._run(
                        ("prefill", bucket), self._prefill_fn(bucket),
                        self._params, self._buffers, jnp.asarray(ids),
                        jnp.int32(req.prompt_len), _flatten(blk.caches),
                        jnp.float32(self.temperature), key,
                    )
                    blk.caches = _unflatten(new_flat, self.config)
            else:
                # the prefill pool already ran the bucket program; the
                # wire block adopts through the SAME compiled scatter,
                # and its first token is the host's already
                first, new_flat = remote
            if psp is not None:
                psp.finish()
            with _RequestPhase("adopt", handle, bucket=bucket,
                               span={"bucket": bucket}):
                # adopt: the request's FRESH pages within the bucket
                # span land in the claim; shared by-reference pages
                # (indices < n_ref) and block pad pages scatter to
                # garbage page 0 — a shared page is never written
                page_ids = np.zeros((bucket // ps,), np.int32)
                k1 = min(n_init, bucket // ps)
                page_ids[n_ref:k1] = row_pages[n_ref:k1]
                feed = self._adopt(bucket, new_flat, jnp.asarray(page_ids),
                                   jnp.int32(row), first=first)
            if self.prefix_cache is not None:
                # publish-on-admission: full prompt pages are stable
                # the moment prefill wrote them (decode writes start at
                # prompt_len, past every full prompt page) — concurrent
                # same-prefix requests hit immediately
                self.prefix_cache.publish(
                    req.input_ids, req.prompt_len, row_pages,
                    self.weights_version,
                )
                self.prefix_cache.update_gauges()
        except BaseException:
            if psp is not None:
                psp.finish(error="admission_error")
            if row is not None:
                self._tables[row, :] = 0
                self._free_rows.append(row)
            if owned:
                self.page_pool.release(owned)
            self._drop_block(blk)
            raise
        if blk is not None:
            self.pool.free(blk)
        self._row_pages[row] = row_pages
        self._row_meta[row] = (
            tuple(int(t) for t in req.input_ids), req.prompt_len
        )
        self._seat(row, handle, first, feed, key, now, t_pre)

    # ------------------------------------------------------- AOT warmup
    def warmup(self, aot_cache=None, buckets=None):
        """Extend the base warmup with the prefix-cache warm path: the
        per-bucket gather-pages program and the per-(bucket,
        tail-bucket) chunked-prefill ladder. Without this the FIRST
        warm hit per shape paid one untracked compile mid-request (the
        PR 14 residual) — now the whole warm-path inventory compiles
        (or AOT-cache-loads) before READY, and the trace guard's
        ``serving::gather_pages`` / ``serving::chunk_prefill`` entries
        are recorded up front, so any LATER compile on those keys is a
        storm finding, not silence."""
        stats = super().warmup(aot_cache=aot_cache, buckets=buckets)
        if self.prefix_cache is None:
            return stats
        from ..jit import aot_cache as aot_mod

        cache = aot_mod.resolve(aot_cache)
        if buckets is None:
            buckets = self._warmup_buckets()
        try:
            for b in buckets:
                ps = self.page_size
                gargs = (self._flat,
                         jnp.zeros((b // ps,), jnp.int32))
                self._warm_one(
                    cache, f"gather_b{b}", ("gather", b),
                    self._gather_fn(b), gargs,
                    lambda comp, b=b: self._gather_fns
                    .__setitem__(b, comp), stats,
                )
                blk = self.pool.alloc(b)
                try:
                    flat = _flatten(blk.caches)
                    for tb in self._tail_buckets(b):
                        cargs = (
                            self._params, self._buffers,
                            jnp.zeros((1, tb), jnp.int32),
                            jnp.int32(1), jnp.int32(0), flat,
                            jnp.float32(self.temperature), self._key,
                        )
                        self._warm_one(
                            cache, f"chunk_b{b}_t{tb}",
                            ("chunk", b, tb), self._chunk_fn(b, tb),
                            cargs,
                            lambda comp, b=b, tb=tb: self._chunk_fns
                            .__setitem__((b, tb), comp), stats,
                            donate=(5,),
                        )
                finally:
                    self.pool.free(blk)
            if self.kv_tier is not None:
                # the tier's restore program: a single-page adopt at
                # bucket == page_size (already warmed when page_size
                # equals the smallest prompt bucket — _warm_one
                # dedups on the trace key)
                ps = self.page_size
                self._warm_one(
                    cache, f"adopt_b{ps}", ("adopt", ps),
                    self._adopt_fn(ps),
                    self._adopt_example_args(self._page_block(), ps),
                    lambda comp: self._adopt_fns
                    .__setitem__(ps, comp), stats,
                    donate=(0,),
                )
        finally:
            # lowering traced the bodies — restore concrete weights
            self._restore_net_state()
        return stats

    # ------------------------------------------------------ decode loop
    def _grow_pages(self):
        """Demand growth: before the decode launch, any row whose write
        position IN THAT LAUNCH (``_launch_pos``: one past the host's
        while its last token is in flight) crosses into an unallocated
        page claims one (evicting cold prefixes if needed). A claim
        that still fails sheds THAT request with ``pages_exhausted`` —
        partial tokens kept (one in flight is dropped), terminal event
        fired, nobody else's pages touched. A page claimed for a step
        that is dropped goes back with the row."""
        ps = self.page_size
        for i in range(self.max_batch_size):
            pos = self._launch_pos(i)
            if pos is None:
                continue
            pages = self._row_pages[i]
            while pos // ps >= len(pages):
                try:
                    new = self._claim_pages(1)
                except PagesExhausted:
                    self.metrics.sheds.inc(label=REASON_PAGES_EXHAUSTED)
                    self._finish(i, CANCELLED,
                                 reason=REASON_PAGES_EXHAUSTED)
                    break
                self._tables[i, len(pages)] = new[0]
                pages.append(new[0])

    def _decode_once(self):
        if self._demand_paging:
            with profiler.RecordEvent("serving::grow_pages"):
                self._grow_pages()
        super()._decode_once()

    def close(self):
        super().close()
        if self.prefix_cache is not None:
            self.prefix_cache.flush(reason="engine_closed")
        if self.prefill_transport is not None:
            self.prefill_transport.close()
        self._tables = None
        self._row_pages = [None] * self.max_batch_size
        self._row_meta = [None] * self.max_batch_size
