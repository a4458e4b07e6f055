"""Bucketed KV-cache pool for the serving engine.

Serving-time cache residency has two shapes of allocation:

- **Blocks** — per-request prefill caches. Prompt lengths are rounded up
  to power-of-two buckets so the number of compiled prefill programs is
  O(log S_max) instead of O(#distinct prompt lengths), and freed blocks
  are recycled *within their bucket* so steady-state serving allocates
  nothing. Recycled buffers are NOT zeroed: the decode position mask
  guarantees a slot is never read before it is written (stale finite
  values sit behind a -inf mask, contributing exactly 0 through the
  fp32 softmax), so scrubbing would be pure overhead.
- **Slabs** — the engine's resident fixed-shape decode buffer
  ([num_slots, S_max, *trailing] for every array a layer's cache
  statement names: Llama's [.., kvH, D] x2). Claim/release of slots
  flows through the pool so occupancy accounting covers the whole
  serving cache footprint in one place.

Dtype default is bf16 (``models.generation.DEFAULT_CACHE_DTYPE``) —
half the HBM of the old unconditional fp32 caches; the attention path
upcasts at the matmul. Layout is owned by
``models.generation.alloc_kv_caches`` so the pool, the whole-decode
programs, and the engine can never drift apart.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..models.generation import (
    DEFAULT_CACHE_DTYPE,
    alloc_kv_caches,
    cache_row_bytes,
    cache_token_bytes,
    normalize_cache_dtype,
)


def bucket_for(seq_len, min_bucket=16, max_seq_len=None):
    """Smallest power-of-two >= seq_len (floored at ``min_bucket``,
    capped at ``max_seq_len`` when given — a request that fits the cap
    but overshoots the rounded bucket still gets the cap bucket)."""
    if seq_len < 1:
        raise ValueError(f"seq_len must be >= 1, got {seq_len}")
    b = max(int(min_bucket), 1)
    while b < seq_len:
        b <<= 1
    if max_seq_len is not None:
        if seq_len > max_seq_len:
            raise ValueError(
                f"seq_len {seq_len} exceeds max_seq_len {max_seq_len}"
            )
        b = min(b, int(max_seq_len))
    return b


class KVBlock:
    """A bucketed per-request cache handle: ``caches`` is the
    ``alloc_kv_caches`` layout ([1, bucket, *trailing] per array the
    layer's cache statement names; Llama: [1, bucket, kvH, D] x2; a
    net with a recurrent layer also the ``[1, *shape]`` arrays it keeps
    a row, which carry the prompt's final state to adoption)."""

    __slots__ = ("bucket", "caches", "_live")

    def __init__(self, bucket, caches):
        self.bucket = bucket
        self.caches = caches
        self._live = True


class SlotSlab:
    """The engine's resident decode buffer viewed as claimable slots.

    The slab's arrays live on the engine (they are jit carry state);
    the slab tracks which rows are claimed and reports into the pool's
    occupancy. ``claim()`` returns a free row index or None."""

    def __init__(self, pool, num_slots, seq_len):
        self._pool = pool
        self.num_slots = int(num_slots)
        self.seq_len = int(seq_len)
        self._free = list(range(int(num_slots)))[::-1]  # pop -> slot 0 first
        self._claimed = set()

    def claim(self):
        if not self._free:
            return None
        slot = self._free.pop()
        self._claimed.add(slot)
        return slot

    def release(self, slot):
        if slot not in self._claimed:
            raise ValueError(f"slot {slot} is not claimed (double free?)")
        self._claimed.remove(slot)
        self._free.append(slot)

    @property
    def claimed(self):
        return len(self._claimed)

    @property
    def free_slots(self):
        return len(self._free)


class KVCachePool:
    """Bucketed KV-cache pool: power-of-two prefill blocks with
    per-bucket freelists + slot accounting for engine decode slabs.

    ``occupancy`` is the number of LIVE allocations (blocks handed out
    and not yet freed, plus claimed slab slots); a drained server must
    read 0 — the tier-1 serving test pins that (zero slot leaks)."""

    def __init__(self, config, *, dtype=None, min_bucket=16,
                 max_seq_len=4096, max_blocks=None):
        self.config = config
        self.dtype = jnp.dtype(normalize_cache_dtype(dtype))
        self.min_bucket = int(min_bucket)
        self.max_seq_len = int(max_seq_len)
        self.max_blocks = max_blocks  # live-block cap (None = unbounded)
        self._freelists = {}   # bucket -> [KVBlock]
        self._live_blocks = 0
        self._block_bytes = 0  # all blocks ever created (resident)
        self._slabs = []
        # counters for metrics/introspection
        self.allocs = 0
        self.reuse_hits = 0

    # ------------------------------------------------------------ blocks
    def bucket_for(self, seq_len):
        return bucket_for(seq_len, self.min_bucket, self.max_seq_len)

    def alloc(self, seq_len):
        """A KVBlock whose bucket covers ``seq_len``. Reuses a freed
        block of the same bucket when one exists."""
        if self.max_blocks is not None and (
            self._live_blocks >= self.max_blocks
        ):
            raise PoolExhausted(
                f"KV pool block cap reached ({self.max_blocks} live)"
            )
        bucket = self.bucket_for(seq_len)
        free = self._freelists.get(bucket)
        if free:
            blk = free.pop()
            blk._live = True
            self.reuse_hits += 1
        else:
            blk = KVBlock(
                bucket,
                alloc_kv_caches(self.config, 1, bucket, self.dtype),
            )
            self.allocs += 1
            self._block_bytes += self._bytes(bucket)
        self._live_blocks += 1
        return blk

    def free(self, block):
        if not block._live:
            raise ValueError("KVBlock double-free")
        block._live = False
        self._freelists.setdefault(block.bucket, []).append(block)
        self._live_blocks -= 1

    def discard(self, block):
        """Retire a block WITHOUT recycling its buffers — for blocks
        whose arrays may be invalid (e.g. donated into a compiled call
        that then failed: the donation consumed the buffers, and
        freelisting them would poison every later alloc in the
        bucket)."""
        if not block._live:
            raise ValueError("KVBlock double-free")
        block._live = False
        block.caches = None
        self._live_blocks -= 1
        self._block_bytes -= self._bytes(block.bucket)

    # ------------------------------------------------------------- slabs
    def alloc_slab_arrays(self, num_slots, seq_len):
        """The engine decode buffer in the shared cache layout
        ([num_slots, seq_len, *trailing] per stated array, pool
        dtype)."""
        return alloc_kv_caches(self.config, num_slots, seq_len, self.dtype)

    def register_slab(self, num_slots, seq_len):
        slab = SlotSlab(self, num_slots, seq_len)
        self._slabs.append(slab)
        return slab

    # ------------------------------------------------------- accounting
    @property
    def occupancy(self):
        """Live allocations: outstanding blocks + claimed slab slots."""
        return self._live_blocks + sum(s.claimed for s in self._slabs)

    def _bytes(self, bucket, rows=1):
        # int8 counts its per-token fp32 scale overhead — residency
        # numbers must not flatter quantized caches; what a net keeps a
        # row whatever its length (a recurrent state) counts once a row
        return rows * (bucket * cache_token_bytes(self.config, self.dtype)
                       + cache_row_bytes(self.config, self.dtype))

    def stats(self):
        free_blocks = sum(len(v) for v in self._freelists.values())
        # resident = every block ever created (live + freelist; freed
        # blocks stay mapped for reuse) + the registered decode slabs
        reserved = self._block_bytes + sum(
            self._bytes(s.seq_len, s.num_slots) for s in self._slabs
        )
        return {
            "dtype": str(self.dtype),
            "live_blocks": self._live_blocks,
            "free_blocks": free_blocks,
            "claimed_slots": sum(s.claimed for s in self._slabs),
            "slab_slots": sum(s.num_slots for s in self._slabs),
            "occupancy": self.occupancy,
            "reserved_bytes": int(reserved),
            "allocs": self.allocs,
            "reuse_hits": self.reuse_hits,
        }


class PoolExhausted(RuntimeError):
    """Raised when the pool's live-block cap is hit (backpressure)."""
