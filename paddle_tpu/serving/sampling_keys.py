"""Per-request PRNG key derivation — ONE scheme for every engine.

Before this module each engine advanced a private ``jax.random.split``
chain per program launch, so a sampled token's randomness depended on
the global interleaving of prefills and decode steps — reproducible
only replay-for-replay on the SAME engine, and never comparable across
the slab and paged engines (their launch orders differ). Speculative
decoding makes that untenable: rejection sampling consumes a variable
number of uniforms per emitted token, and the pinned guarantee (the
output distribution equals vanilla sampling) is only testable when the
randomness is addressable by WHAT is being sampled, not by when.

The scheme (pure ``fold_in`` tree, no mutable chain):

- ``request key`` = ``fold_in(PRNGKey(seed), admission_index)`` — the
  engine-local admission counter, NOT the process-global request id
  (two engines fed the same workload in the same order derive the same
  request keys; the global id would desynchronize them).
- ``position key`` = ``fold_in(request_key, j)`` where ``j`` is the
  cache position the sampled token will occupy. Prefill samples the
  token at ``j = prompt_len``; a chunked prefill at offset ``pos``
  samples ``j = pos + tail_len`` — the SAME position, which is what
  keeps the warm (chunked) path bitwise-equal to the cold path. Decode
  at position ``pos`` samples ``j = pos + 1``. Program bodies do the
  position fold INSIDE the jit (vector ``pos`` folds per row via vmap).
- speculative purposes fold one more constant below the position key:
  draft proposal / acceptance uniform / residual resample each draw
  from a disjoint stream, so speculation never consumes (or collides
  with) the vanilla stream's randomness at any position.

Determinism pin (tier-1): the slab and paged engines produce
IDENTICAL sampled streams for the same seed and submission order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# speculative purpose folds (any distinct constants; folded below the
# position key so the undecorated position key IS the vanilla stream)
DRAFT = 0x5D
ACCEPT = 0x5E
RESIDUAL = 0x5F


class SamplingKeySource:
    """Derives one base key per admitted request off a master seed.

    The counter is the engine-local ADMISSION index: it advances once
    per ``_admit_one``, in admission order — the same order on every
    engine geometry for a fixed workload (the scheduler is strict
    priority-FIFO), which is what makes sampled streams comparable
    across backends.

    A key is handed out as a host array: the engine stacks the rows'
    keys on the host a decode launch, and an admission must not wait
    for the device, which is busy with the step in flight. So the
    keys are derived ``_AHEAD`` admissions at a time and read once."""

    _AHEAD = 64

    def __init__(self, seed):
        self._master = jax.random.PRNGKey(int(seed))
        self.next_index = 0
        self._first = 0         # the admission index of _keys[0]
        self._keys = ()

    def next_request_key(self):
        i = self.next_index - self._first
        if not 0 <= i < len(self._keys):
            self._first, i = self.next_index, 0
            self._keys = np.asarray(jax.vmap(
                jax.random.fold_in, (None, 0))(
                    self._master,
                    self._first + jnp.arange(self._AHEAD, dtype=jnp.uint32)))
        self.next_index += 1
        return self._keys[i]


def position_key(request_key, position):
    """The key that samples the token landing at cache ``position`` —
    host-side mirror of the fold the program bodies apply."""
    return jax.random.fold_in(request_key, int(position))


def purpose_key(request_key, position, purpose):
    """A speculative sub-stream (DRAFT / ACCEPT / RESIDUAL) at one
    position: disjoint from the vanilla stream by construction."""
    return jax.random.fold_in(position_key(request_key, position),
                              int(purpose))
