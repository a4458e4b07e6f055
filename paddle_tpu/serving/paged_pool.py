"""Block/page KV pool for the paged serving engine.

The slab pool's concurrency problem: a decode slab row is ``S_max``
tokens of resident HBM no matter how short the request, so bucketing
wins (fewer compiles) never became resident-HBM wins (more concurrent
requests per chip). The paged pool fixes the unit of residency: the
cache lives in a PAGE ARENA (``[num_pages, page_size, *trailing]`` for
every array the net's cache statement names, ``generation.cache_layout``:
Llama's K and V of ``[kvH, D]``, a latent-attention net's ONE array)
and a request claims only ``ceil(total_tokens / page_size)`` pages —
its own length, quantized to one page. At equal KV HBM, a mixed-length
workload admits strictly more concurrent requests (the tier-1 test
pins this against the slab engine).

Layout contract:

- Page id **0 is the reserved garbage page**: unallocated page-table
  tail entries and free decode rows point at it, so scatter/gather over
  a fixed ``[B, P_max]`` table never needs a validity branch — garbage
  columns sit behind the position mask (-inf -> exact 0 through the
  fp32 softmax), the same discipline that makes recycled slab blocks
  safe without scrubbing.
- ``page_size`` must be a power of two and divide ``min_bucket`` (hence
  every power-of-two prefill bucket): adoption scatters a prefilled
  ``[1, bucket]`` block as ``bucket // page_size`` whole pages, one
  compiled scatter program per bucket.
- Pages are claimed UP FRONT at admission (``pages_for(total_tokens)``)
  so decode can never fail mid-sequence on page exhaustion; EOS early
  stop releases the whole claim early. The quantization loss is at most
  ``page_size - 1`` tokens per request.

Like the slab pool, the arena ARRAYS live on the engine (they are jit
carry state); the pool owns the freelist and the accounting — a drained
server must read ``pages_in_use == 0`` (zero-leak, tier-1-pinned).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..models.generation import (
    alloc_kv_caches,
    cache_row_bytes,
    cache_token_bytes,
    normalize_cache_dtype,
)


class PagesExhausted(RuntimeError):
    """Raised when a claim cannot be satisfied (admission backpressure;
    the engine treats it as 'leave the request queued')."""


class PagedKVPool:
    """Freelist + accounting over a fixed page arena.

    ``num_pages`` is the number of USABLE pages (the reserved garbage
    page 0 is allocated on top). ``claim(n)`` returns ``n`` page ids or
    raises :class:`PagesExhausted`; ``release(ids)`` returns them.
    Double-release and foreign ids raise — leaks are bugs, not noise.
    """

    def __init__(self, config, *, page_size=16, num_pages, dtype=None,
                 max_seq_len=4096):
        ps = int(page_size)
        if ps < 1 or (ps & (ps - 1)):
            raise ValueError(
                f"page_size must be a power of two, got {page_size}"
            )
        self.config = config
        self.page_size = ps
        self.num_pages = int(num_pages)
        if self.num_pages < 1:
            raise ValueError("need at least one usable page")
        self.max_seq_len = int(max_seq_len)
        # saved-artifact accounting pools carry no model config and
        # never allocate arrays — any dtype name is just a label there
        self.dtype = jnp.dtype(
            normalize_cache_dtype(dtype) if config is not None
            else (dtype or "bfloat16")
        )
        # ids 1..num_pages are claimable; 0 is the garbage page
        self._free = list(range(1, self.num_pages + 1))[::-1]
        # page id -> refcount. A fresh claim holds one reference; the
        # prefix cache and every request adopting a shared page hold one
        # more each (incref). release() decrements; the page returns to
        # the freelist only when the LAST reference drops — copy-on-
        # write page sharing without a separate ownership ledger.
        self._refs = {}
        # counters for metrics/introspection
        self.claims = 0
        self.releases = 0
        self.increfs = 0
        self.exhausted_events = 0
        self.peak_in_use = 0
        # incremental sum of max(0, refcount - 2) over all pages: every
        # reference past (cache + first holder) is a private page copy
        # sharing avoided — the shared-HBM-saved gauge reads this O(1)
        # instead of walking the cache per request
        self._extra_shared_refs = 0

    # --------------------------------------------------------- geometry
    def pages_for(self, total_tokens):
        """Pages a request of ``total_tokens`` (prompt + max_new) needs."""
        if total_tokens < 1:
            raise ValueError("total_tokens must be >= 1")
        return -(-int(total_tokens) // self.page_size)

    def table_width(self):
        """P_max: page-table columns covering ``max_seq_len`` logical
        slots (the compiled decode step's fixed table shape)."""
        return -(-self.max_seq_len // self.page_size)

    def alloc_arena_arrays(self, rows=0):
        """The page arena in the shared cache layout: for every array
        the config's cache statement names
        (``generation.cache_layout``), ``[num_pages + 1, page_size,
        *trailing]`` (row 0 = garbage page), pool dtype — Llama's
        ``[.., kvH, D]`` x2 a layer, a latent net's one ``[.., latent +
        rope dims]``. Behind them in a layer's tuple lie the arrays the
        net keeps a ROW (``generation.row_layout``: a recurrent state),
        ``[rows, *shape]`` for the engine's ``rows`` decode rows: pages
        cannot hold them, the page table does not address them, and a
        net that states none gets none. The arena IS the batch-of-pages
        view of
        ``alloc_kv_caches``: an int8 pool gets quantized storage there
        (int8 values + per-(slot, kvH) fp32 scales as one
        ``QuantizedKV`` pytree per array; zero scales keep the garbage
        page dequantizing to exact zeros)."""
        return alloc_kv_caches(self.config, self.num_pages + 1,
                               self.page_size, self.dtype, rows=rows)

    # ------------------------------------------------------- claim flow
    @property
    def free_pages(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return len(self._refs)

    @property
    def shared_pages(self):
        """Pages held by more than one reference (a cached prefix page
        adopted by at least one live request, or the cache plus its
        publisher)."""
        return sum(1 for v in self._refs.values() if v > 1)

    @property
    def shared_saved_pages(self):
        """Private page copies avoided by sharing RIGHT NOW: references
        past (cache + first holder) per page, maintained incrementally
        — O(1) to read from any thread."""
        return self._extra_shared_refs

    def refcount(self, page_id):
        return self._refs.get(int(page_id), 0)

    def claim(self, n):
        """``n`` fresh page ids (refcount 1 each), or raise
        :class:`PagesExhausted` (nothing is claimed on failure — no
        partial claims to unwind)."""
        n = int(n)
        if n < 1:
            raise ValueError(f"claim of {n} pages")
        if n > len(self._free):
            self.exhausted_events += 1
            raise PagesExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"({len(self._refs)} in use)"
            )
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._refs[i] = 1
        self.claims += n
        self.peak_in_use = max(self.peak_in_use, len(self._refs))
        return ids

    def incref(self, ids):
        """Adopt already-claimed pages by reference (prefix sharing:
        the cache's hold on a published page, a request's hold on an
        adopted one). Validated all-or-nothing like :meth:`release`."""
        ids = [int(i) for i in ids]
        bad = [i for i in ids if i not in self._refs]
        if bad:
            raise ValueError(
                f"page(s) {bad} not claimed — cannot share an "
                f"unclaimed page"
            )
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate page ids in one incref: {ids}")
        for i in ids:
            if self._refs[i] >= 2:
                self._extra_shared_refs += 1
            self._refs[i] += 1
        self.increfs += len(ids)

    def release(self, ids):
        """Drop one reference per id. The WHOLE id list is validated
        before anything is touched — a raise means nothing was
        released, so a caller may safely treat the claim as still held.
        A page returns to the freelist only when its LAST reference
        drops (``releases`` counts freelist returns, so a fully drained
        pool always reads ``claims == releases`` — the zero-leak pin)."""
        ids = [int(i) for i in ids]
        bad = [i for i in ids if i not in self._refs]
        if bad:
            raise ValueError(
                f"page(s) {bad} not claimed (double release or foreign "
                f"id?)"
            )
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate page ids in one release: {ids}")
        for i in ids:
            if self._refs[i] >= 3:
                self._extra_shared_refs -= 1
            self._refs[i] -= 1
            if self._refs[i] == 0:
                del self._refs[i]
                self._free.append(i)
                self.releases += 1

    # ------------------------------------------------------- accounting
    def page_bytes(self):
        """HBM bytes of ONE page across every layer's arena arrays.
        0 when the pool was built without a model config (the saved-
        artifact accounting path — page counts still tally, byte
        figures degrade honestly instead of guessing)."""
        cfg = self.config
        if cfg is None:
            return 0
        # int8 pages count their per-token fp32 scale overhead: the
        # equal-HBM concurrency comparison must not flatter quantization
        return self.page_size * cache_token_bytes(cfg, self.dtype)

    def row_bytes(self):
        """HBM bytes ONE decode row keeps beside the pages, whatever
        its length (``generation.row_layout``); 0 for a net that keeps
        its whole cache by token. Allocated once a row with the arena
        and never claimed or released: page accounting does not count
        them."""
        if self.config is None:
            return 0
        return cache_row_bytes(self.config, self.dtype)

    def request_resident_bytes(self, total_tokens):
        """Resident KV bytes one admitted request costs in this pool —
        the number the slab-vs-paged concurrency test compares against
        the slab's unconditional ``S_max`` row."""
        return self.pages_for(total_tokens) * self.page_bytes()

    def arena_bytes(self):
        """Total arena residency (usable pages + the garbage page)."""
        return (self.num_pages + 1) * self.page_bytes()

    def stats(self):
        return {
            "dtype": str(self.dtype),
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "table_width": self.table_width(),
            "free_pages": self.free_pages,
            "pages_in_use": self.pages_in_use,
            "shared_pages": self.shared_pages,
            "peak_pages_in_use": self.peak_in_use,
            "increfs": self.increfs,
            "page_bytes": self.page_bytes(),
            "row_bytes": self.row_bytes(),
            "arena_bytes": self.arena_bytes(),
            "claims": self.claims,
            "releases": self.releases,
            "exhausted_events": self.exhausted_events,
        }
