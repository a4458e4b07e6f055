"""Speculative decoding — draft-propose, one-shot verify, exact streams.

Decode's serial bottleneck is one full-model forward per emitted token.
Speculative decoding issues FEWER serial target steps: a cheap draft
model proposes K tokens autoregressively, then the target scores all
K+1 positions in ONE program launch and a host-side acceptance rule
keeps the longest valid prefix — every round emits between 1 and K+1
tokens with exactly one target-verify launch.

The verify program is the heart, and its construction is dictated by a
measured numerics fact (see ``tests/test_speculative.py``): a chunked
forward's LOGITS are not bitwise-equal to sequential decode logits
(fp32 ulp drift from the different matmul shapes), but its bf16 KV
WRITES are — bf16 rounding absorbs the drift. So the verify body runs
two passes in one jitted program:

1. **chunk-write**: the K+1 tokens ``[last, d_1..d_K]`` run through the
   cache path at positions ``[pos, pos+K]`` (head skipped) — this
   writes the same bf16 KV a sequential decode would have written;
2. **broadcast re-read**: the written block is broadcast to K+1 batch
   rows and ONE decode-shaped step scores row ``i`` at position
   ``pos+i`` — decode-shaped attention over decode-written KV, bitwise
   identical to vanilla decode logits (row independence across batch
   size is the engine's core pinned invariant).

int8 KV stores per-token fp32 SCALES, which keep the chunk pass's ulp
drift, so for quantized caches the verify body instead unrolls K+1
sequential decode sub-steps inside one program — the vanilla data flow
exactly (bitwise by construction), amortizing dispatch rather than
FLOPs. Greedy speculative streams are therefore EXACT-EQUAL to vanilla
decode on bf16 AND int8 engines (tier-1-pinned).

For ``temperature > 0`` acceptance is the Leviathan/Chen rejection
rule: accept ``d_i`` iff ``U < p(d_i)/q(d_i)``, resample the first
rejection from ``norm(max(p - q, 0))``, bonus-sample from ``p_K`` when
everything is accepted — the emitted distribution EQUALS vanilla
sampling (chi-square-pinned), with every uniform drawn from the
position-addressed key tree in ``sampling_keys`` so slab and paged
engines emit identical speculative sampled streams.

Drafts: a separate small llama, or the draft-free SELF-speculative
variant — ``exit_layer=N`` runs the target's first N layers + the
shared head through the ``LlamaModel.forward(exit_layer=)`` seam (its
own N-layer KV cache, zero extra weights).

Engine integration is per-row: with speculation bound, each engine
step runs one propose+verify round per active row through backend
hooks (``_spec_gather`` / ``_spec_adopt`` / ``_spec_reserve`` /
``_spec_rollback``) — the paged engine's verify runs through the
bucketed gather -> verify -> adopt-pages pipeline into pages the
request owns, demand-claims transient pages for the proposed tail and
releases the rejected tail's pages on rollback (zero-leak-pinned).
The whole speculative program inventory — draft prefill per bucket,
draft decode, steady-state verify per block width, the KV gather —
pre-compiles in ``engine.warmup()`` and persists through the AOT
compile cache (``jit/aot_cache.py``), so the first speculative round
pays zero compiles. Known gaps: no tree/Medusa multi-branch drafts;
per-row rounds trade batched-decode throughput for latency (the win
is measured at low concurrency).
"""
from __future__ import annotations

import copy

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiler
from ..core import tape
from ..core.tensor import Tensor
from ..models.generation import (
    alloc_kv_caches,
    decode_step,
    filter_logits,
    prefill,
)
from ..observability.tracing import get_tracer
from ..quantization.kv import broadcast_rows
from .sampling_keys import ACCEPT, DRAFT, RESIDUAL, position_key, purpose_key


def _flatten(caches):
    return [a for kv in caches for a in kv]


def _unflatten(flat):
    """K/V pairs a layer: speculation is refused at construction over
    any other cache statement (``ServingEngine._kv_pair_features``),
    and an early-exit draft keeps fewer layers than its config says."""
    return [(flat[2 * i], flat[2 * i + 1]) for i in range(len(flat) // 2)]


class _EarlyExitDraft:
    """The self-speculative draft: the target's first ``exit_layer``
    decoder layers + final norm + the shared lm_head, presented through
    the same callable surface ``prefill``/``decode_step`` drive. Its
    ``config`` is a truncated copy so draft caches allocate exactly
    ``exit_layer`` layer pairs."""

    def __init__(self, target, exit_layer):
        n = int(exit_layer)
        if not 1 <= n <= target.config.num_hidden_layers:
            raise ValueError(
                f"exit_layer {exit_layer} outside [1, "
                f"{target.config.num_hidden_layers}]"
            )
        self.target = target
        self.exit_layer = n
        self.config = copy.copy(target.config)
        self.config.num_hidden_layers = n

    def __call__(self, input_ids, attn_mask=None, caches=None, pos=None,
                 page_table=None):
        kw = {} if page_table is None else {"page_table": page_table}
        return self.target(input_ids, attn_mask, caches=caches, pos=pos,
                           exit_layer=self.exit_layer, **kw)

    def load_functional_state(self, params, buffers):
        self.target.load_functional_state(params, buffers)

    def eval(self):
        self.target.eval()


# ------------------------------------------------------- acceptance math
#
# Host-side and numpy/eager-jax only: the verify program returns raw
# logits rows; everything below is deterministic given those rows and
# the request's position-addressed keys, so both engines compute
# identical outcomes (the cross-backend determinism pin).


def _dist(row, temperature, top_k, top_p):
    """One logits row [V] -> normalized fp32 probabilities over the
    SAME filtered support the compiled sampling head uses."""
    f = np.asarray(filter_logits(jnp.asarray(row)[None, :],
                                 jnp.float32(temperature), top_k, top_p))[0]
    f = f - np.max(f)
    p = np.exp(f, dtype=np.float64)
    p[~np.isfinite(f)] = 0.0
    return p / p.sum()


def _sample(probs, key):
    """Exact inverse-CDF draw from ``probs`` with one uniform off
    ``key`` — the host mirror of one categorical draw."""
    u = float(jax.random.uniform(key))
    cdf = np.cumsum(probs)
    return int(min(np.searchsorted(cdf, u * cdf[-1], side="right"),
                   len(probs) - 1))


def accept_greedy(target_rows, props):
    """Greedy token-match acceptance: ``target_rows`` [K+1, V] are the
    verify logits at positions pos..pos+K, ``props`` the K draft
    tokens. Returns (accepted_count, emitted tokens) — always emits
    accepted + 1 (the correction/bonus token from the first unmatched
    row), so a round never stalls."""
    a = 0
    for i, d in enumerate(props):
        if int(np.argmax(target_rows[i])) != int(d):
            break
        a += 1
    emitted = [int(t) for t in props[:a]]
    emitted.append(int(np.argmax(target_rows[a])))
    return a, emitted


def accept_sampled(target_rows, draft_rows, props, request_key, pos,
                   temperature, top_k, top_p):
    """Rejection-sampling acceptance (Leviathan/Chen): the emitted
    token distribution is EXACTLY vanilla sampling from the filtered
    target distribution, position by position. ``target_rows`` [K+1,V],
    ``draft_rows`` [K, V] (the draft's proposal logits), ``props`` the
    K proposed tokens; position ``pos`` is the verify round's base (the
    token at pos is the last emitted one). Returns
    (accepted_count, emitted)."""
    a = 0
    emitted = []
    for i, d in enumerate(props):
        d = int(d)
        p = _dist(target_rows[i], temperature, top_k, top_p)
        q = _dist(draft_rows[i], temperature, top_k, top_p)
        u = float(jax.random.uniform(
            purpose_key(request_key, pos + i + 1, ACCEPT)
        ))
        if q[d] > 0 and u * q[d] <= p[d]:
            a += 1
            emitted.append(d)
            continue
        residual = np.maximum(p - q, 0.0)
        if residual.sum() <= 0:
            residual = p  # p == q exactly: any draw is distribution-true
        emitted.append(_sample(
            residual, purpose_key(request_key, pos + i + 1, RESIDUAL)
        ))
        return a, emitted
    # everything accepted: the bonus token comes from the verify's last
    # row — the VANILLA position key, so an all-accept round consumes
    # the same stream address vanilla decode would have
    p = _dist(target_rows[len(props)], temperature, top_k, top_p)
    emitted.append(_sample(
        p, position_key(request_key, pos + len(props) + 1)
    ))
    return a, emitted


# ------------------------------------------------------- verify programs


def build_verify_body(net, k1, sequential):
    """The one-launch verify program body over a ``[1, W]`` KV block:
    ``ids`` [1, k1] at positions [pos, pos+k1). ``sequential=False`` is
    the parallel two-pass construction (bf16/fp32 — chunk-write then
    broadcast re-read); ``sequential=True`` unrolls k1 decode sub-steps
    (int8 — per-token fp32 scales keep chunk-shape ulps, so the verify
    must BE the vanilla data flow). Returns (logits [k1, V], block)."""

    if sequential:
        def body(params, buffers, ids, flat_block, pos):
            net.load_functional_state(params, buffers)
            net.eval()
            p = jnp.asarray(pos, jnp.int32)
            caches = _unflatten(flat_block)
            rows = []
            for i in range(k1):
                lg, caches = decode_step(
                    net, ids[:, i:i + 1], caches, p + i
                )
                rows.append(lg)
            return jnp.concatenate(rows, 0), _flatten(caches)

        return body

    def body(params, buffers, ids, flat_block, pos):
        net.load_functional_state(params, buffers)
        net.eval()
        p = jnp.asarray(pos, jnp.int32)
        with tape.trace_scope(), tape.no_grad():
            _, caches = net.model(
                Tensor(ids), None, caches=_unflatten(flat_block), pos=p,
                apply_final_norm=False,
            )
        flat2 = _flatten(caches)
        rows = _unflatten([broadcast_rows(a, k1) for a in flat2])
        logits, _ = decode_step(
            net, jnp.transpose(ids), rows,
            p + jnp.arange(k1, dtype=jnp.int32),
        )
        return logits, flat2

    return body


class _DraftSlot:
    """Per-engine-row draft cache state. ``fed`` counts tokens the
    draft has consumed (cache positions [0, fed) are valid); -1 marks a
    retired/fresh row whose next round re-ingests the full context.
    The arrays persist across requests — stale content sits behind the
    position mask until overwritten, the slab-recycling discipline."""

    __slots__ = ("flat", "fed")

    def __init__(self):
        self.flat = None
        self.fed = -1


class SpeculativeDecoder:
    """Pairs a draft with the target inside a serving engine.

    ``draft``: a small causal LM sharing the target's tokenizer space,
    OR ``exit_layer=N`` for the draft-free self-speculative variant.
    ``k`` is the proposal depth — each round emits 1..k+1 tokens for
    one verify launch. Construct, pass as ``speculative=`` to either
    engine, and the engine binds it at init."""

    def __init__(self, draft=None, *, k=4, exit_layer=None,
                 draft_cache_dtype="bfloat16"):
        if (draft is None) == (exit_layer is None):
            raise ValueError(
                "pass exactly one of draft= (a small causal LM) or "
                "exit_layer= (self-speculative early exit)"
            )
        if int(k) < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = int(k)
        self.exit_layer = None if exit_layer is None else int(exit_layer)
        self.draft_cache_dtype = draft_cache_dtype
        self._draft_arg = draft
        self._eng = None
        self._draft = None
        self._dparams = None
        self._dbuffers = None
        self._draft_traced = set()
        self._draft_prefill_fns = {}
        self._draft_decode_fn = None
        self._verify_fns = {}
        self._slots = {}
        self._sequential = False
        # running stats (the /healthz block + stats())
        self.rounds = 0
        self.proposed = 0
        self.accepted = 0
        self.emitted = 0
        self.draft_ingests = 0

    @property
    def mode(self):
        return "self" if self.exit_layer is not None else "draft"

    # ------------------------------------------------------------ binding
    def bind(self, engine):
        """Attach to one engine (called from the engine's __init__):
        resolve the draft, snapshot its weights, and widen the
        engine's recompile-storm bar to the speculative program
        inventory (per-bucket draft prefill + per-width verify +
        draft decode)."""
        if self._eng is not None:
            raise RuntimeError(
                "SpeculativeDecoder is already bound to an engine"
            )
        self._eng = engine
        if self.exit_layer is not None:
            self._draft = _EarlyExitDraft(engine.net, self.exit_layer)
            # self-spec shares the target snapshot (refreshed on reload)
            self._dparams = engine._params
            self._dbuffers = engine._buffers
        else:
            self._draft = self._draft_arg
            if self._draft.config.vocab_size != engine.config.vocab_size:
                raise ValueError(
                    f"draft vocab {self._draft.config.vocab_size} != "
                    f"target vocab {engine.config.vocab_size}"
                )
            self._dparams = {
                k: p.value for k, p in self._draft.named_parameters()
            }
            self._dbuffers = {
                k: b.value for k, b in self._draft.named_buffers()
            }
        self._sequential = jnp.dtype(engine.cache_dtype) == jnp.int8
        # speculative program inventory: draft prefill per bucket,
        # verify per (block width, chunk length) — chunk length is
        # k+1 in steady state, smaller only on the last round(s) of a
        # request — plus draft decode and the gather program(s)
        # (per-bucket on the paged engine, warmed up front)
        nb = len(engine._warmup_buckets())
        engine.trace_guard.max_compiles += nb * (self.k + 3) + 4

    def unbind(self):
        """Engine close: drop compiled programs and draft state."""
        self._eng = None
        self._draft_prefill_fns.clear()
        self._draft_decode_fn = None
        self._verify_fns.clear()
        self._slots.clear()
        self._draft_traced.clear()

    def on_weights_swapped(self, engine):
        """Live reload landed: the self-speculative draft serves the
        NEW snapshot, and every draft cache (computed under the old
        weights) is invalidated — next rounds re-ingest."""
        if self.exit_layer is not None:
            self._dparams = engine._params
            self._dbuffers = engine._buffers
        for st in self._slots.values():
            st.fed = -1

    def reset_slot(self, slot):
        """Row retired (request finished/cancelled): the draft cache
        arrays stay (recycled behind the position mask), the state is
        marked fresh."""
        st = self._slots.get(slot)
        if st is not None:
            st.fed = -1

    def stats(self):
        return {
            "mode": self.mode,
            "k": self.k,
            "exit_layer": self.exit_layer,
            "sequential_verify": self._sequential,
            "rounds": self.rounds,
            "proposed": self.proposed,
            "accepted": self.accepted,
            "emitted": self.emitted,
            "draft_ingests": self.draft_ingests,
            "mean_accept_length": (
                round(self.emitted / self.rounds, 3) if self.rounds
                else None
            ),
        }

    def reset_stats(self):
        """Zero the running counters (serve_bench calls this after its
        off-the-clock warmup so acceptance stats cover only the timed
        replay)."""
        self.rounds = self.proposed = 0
        self.accepted = self.emitted = 0
        self.draft_ingests = 0

    def signature(self):
        """The AOT-cache key extra for ``spec_*`` programs: every knob
        that changes a traced speculative program body. A cache hit
        across different draft geometries would install the wrong
        executable."""
        sig = {
            "mode": self.mode,
            "k": self.k,
            "exit_layer": self.exit_layer,
            "draft_cache_dtype": str(self.draft_cache_dtype),
            "sequential": self._sequential,
        }
        if self.exit_layer is None and self._draft is not None:
            dc = self._draft.config
            sig["draft_model"] = {
                "vocab": int(dc.vocab_size),
                "hidden": int(dc.hidden_size),
                "inter": int(dc.intermediate_size),
                "layers": int(dc.num_hidden_layers),
                "heads": int(dc.num_attention_heads),
                "kv_heads": int(dc.kv_heads),
            }
        return sig

    # ------------------------------------------------------- AOT warmup
    def warmup(self, eng, cache, stats, buckets):
        """Pre-compile (or AOT-cache-load) the whole speculative
        inventory before first traffic — called from the engine's
        ``warmup()`` with its cache/stats so the programs ride the
        same persistence and show in the same ``program_memory``
        table. Warms: draft prefill per prompt bucket, the draft
        decode step, the verify program per (block width, k+1) plus
        the (width, 1) last-round shape, and the backend's KV gather
        program(s). Every compile lands on a trace-guard key recorded
        at build time, so a LATER compile on those keys is a storm
        finding."""
        dp, db = self._dparams, self._dbuffers
        dflat = _flatten(alloc_kv_caches(
            self._draft.config, 1, eng.max_seq_len,
            self.draft_cache_dtype,
        ))
        try:
            for b in buckets:
                eng._warm_one(
                    cache, f"spec_draft_prefill_b{b}",
                    ("spec_dprefill", b), self._draft_prefill(b),
                    (dp, db, jnp.zeros((1, b), jnp.int32), dflat,
                     jnp.int32(b)),
                    lambda comp, b=b: self._draft_prefill_fns
                    .__setitem__(b, comp), stats,
                )
            eng._warm_one(
                cache, "spec_draft_decode", ("spec_ddecode",),
                self._draft_decode(),
                (dp, db, jnp.zeros((1, 1), jnp.int32), dflat,
                 jnp.int32(0)),
                lambda comp: setattr(self, "_draft_decode_fn", comp),
                stats,
            )
            for w in eng._verify_widths(buckets):
                flatb = _flatten(alloc_kv_caches(
                    eng.config, 1, w, eng.cache_dtype,
                ))
                # the whole chunk ladder: k+1 in steady state, every
                # shorter length on a request's final rounds (k_eff
                # clamps to the tokens still owed)
                for k1 in range(1, self.k + 2):
                    eng._warm_one(
                        cache, f"spec_verify_w{w}_k{k1}",
                        ("spec_verify", w, k1),
                        self._verify_fn(w, k1),
                        (eng._params, eng._buffers,
                         jnp.zeros((1, k1), jnp.int32), flatb,
                         jnp.int32(0)),
                        lambda comp, w=w, k1=k1: self._verify_fns
                        .__setitem__((w, k1), comp), stats,
                    )
            eng._warm_spec_gather(cache, stats, buckets)
            # the lowerings above already swapped tracers through the
            # draft's imperative layers once — the first-trace restore
            # below covers them, so runtime _drun need not re-restore
            for b in buckets:
                self._draft_traced.add(("dprefill", b))
            self._draft_traced.add(("ddecode",))
        finally:
            self._restore_draft()

    # ------------------------------------------------- compiled programs
    def _restore_draft(self):
        self._draft.load_functional_state(self._dparams, self._dbuffers)
        self._draft.eval()

    def _drun(self, trace_key, fn, *args):
        """Run a draft program with the engine's restore-after-first-
        trace discipline — tracing swaps tracers into the draft's
        imperative layers (for self-spec those ARE the target's)."""
        out = fn(*args)
        if trace_key not in self._draft_traced:
            self._draft_traced.add(trace_key)
            self._restore_draft()
            if self.exit_layer is not None:
                # the trace ran through the target net: put the
                # ENGINE's concrete state back too
                self._eng._restore_net_state()
        return out

    def _draft_prefill(self, bucket):
        fn = self._draft_prefill_fns.get(bucket)
        if fn is not None:
            return fn
        draft = self._draft

        def body(params, buffers, ids, flat, length):
            draft.load_functional_state(params, buffers)
            draft.eval()
            _, caches = prefill(draft, ids, _unflatten(flat),
                                length=length)
            return _flatten(caches)

        fn = jax.jit(body)
        self._draft_prefill_fns[bucket] = fn
        self._eng.trace_guard.record_compile(
            "serving::spec_draft_prefill", bucket,
            origin="serving/speculative.py",
        )
        return fn

    def _draft_decode(self):
        if self._draft_decode_fn is not None:
            return self._draft_decode_fn
        draft = self._draft

        def body(params, buffers, tok, flat, pos):
            draft.load_functional_state(params, buffers)
            draft.eval()
            logits, caches = decode_step(draft, tok, _unflatten(flat),
                                         pos)
            return logits, _flatten(caches)

        self._draft_decode_fn = jax.jit(body)
        self._eng.trace_guard.record_compile(
            "serving::spec_draft_decode", 1,
            origin="serving/speculative.py",
        )
        return self._draft_decode_fn

    def _verify_fn(self, width, k1):
        """The verify program for a [1, width] block scoring k1
        positions. Sized to the EXACT chunk (no id padding): a padded
        chunk would write cache positions past the reserved span, and
        jax's clamped scatter would land those writes on valid KV."""
        fn = self._verify_fns.get((width, k1))
        if fn is not None:
            return fn
        body = build_verify_body(self._eng.net, k1, self._sequential)
        fn = jax.jit(body)
        self._verify_fns[(width, k1)] = fn
        self._eng.trace_guard.record_compile(
            "serving::spec_verify", (width, k1),
            origin="serving/speculative.py",
        )
        return fn

    # ---------------------------------------------------------- the round
    def _slot_state(self, slot):
        st = self._slots.get(slot)
        if st is None:
            st = self._slots[slot] = _DraftSlot()
        if st.flat is None:
            st.flat = _flatten(alloc_kv_caches(
                self._draft.config, 1, self._eng.max_seq_len,
                self.draft_cache_dtype,
            ))
        return st

    def _full_tok(self, seq, j):
        """Token at sequence position ``j`` (prompt ++ emitted)."""
        req = seq.handle.request
        if j < req.prompt_len:
            return int(req.input_ids[j])
        return int(seq.handle.tokens[j - req.prompt_len])

    def _propose(self, eng, slot, seq, pos, k_eff):
        """Draft side of one round: catch the draft cache up to
        ``pos`` tokens consumed, then propose ``k_eff`` tokens.
        Returns (proposals, draft logits rows)."""
        st = self._slot_state(slot)
        dp, db = self._dparams, self._dbuffers
        if st.fed < 0 or st.fed > pos:
            # fresh row (or invalidated): ingest the full context
            # [0, pos) through the bucketed draft prefill
            bucket = eng.pool.bucket_for(pos)
            ids = np.zeros((1, bucket), np.int32)
            for j in range(pos):
                ids[0, j] = self._full_tok(seq, j)
            with profiler.RecordEvent(
                f"serving::spec_draft_prefill_b{bucket}"
            ):
                st.flat = self._drun(
                    ("dprefill", bucket), self._draft_prefill(bucket),
                    dp, db, jnp.asarray(ids), st.flat, jnp.int32(pos),
                )
            st.fed = pos
            self.draft_ingests += 1
        while st.fed < pos:
            # catch-up (at most one token per round: only a fully
            # accepted round leaves the bonus token unconsumed)
            _, st.flat = self._drun(
                ("ddecode",), self._draft_decode(), dp, db,
                jnp.asarray([[self._full_tok(seq, st.fed)]], jnp.int32),
                st.flat, jnp.int32(st.fed),
            )
            st.fed += 1
        props, qrows = [], []
        t = seq.last_tok
        do_sample = eng.do_sample
        for i in range(k_eff):
            lg, st.flat = self._drun(
                ("ddecode",), self._draft_decode(), dp, db,
                jnp.asarray([[t]], jnp.int32), st.flat,
                jnp.int32(pos + i),
            )
            st.fed = pos + i + 1
            row = np.asarray(lg[0])
            if do_sample:
                d = _sample(
                    _dist(row, eng.temperature, eng.top_k, eng.top_p),
                    purpose_key(jnp.asarray(seq.key), pos + i + 1,
                                DRAFT),
                )
            else:
                d = int(np.argmax(row))
            props.append(d)
            qrows.append(row)
            t = d
        return props, qrows

    def decode_once(self, eng):
        """The engine's decode phase under speculation: one
        propose+verify round per active row (a verify is a bounded-K
        prefill from the scheduler's point of view — chunked-prefill
        ITL bounds hold with chunk length k+1)."""
        for slot in range(eng.max_batch_size):
            if eng._seqs[slot] is not None:
                self._round(eng, slot)

    def _round(self, eng, slot):
        seq = eng._seqs[slot]
        h = seq.handle
        req = h.request
        pos = seq.pos
        remaining = req.max_new_tokens - seq.emitted
        k_eff = min(self.k, remaining - 1)
        # backend capacity: the verify writes KV at [pos, pos+k_eff] —
        # the paged engine demand-claims transient pages here and may
        # clamp (k_eff 0 degenerates to a one-token verify, the exact
        # vanilla-equivalent step)
        k_eff = max(0, eng._spec_reserve(slot, pos + k_eff) - pos)
        t0 = eng.clock()
        props, qrows = ([], [])
        if k_eff:
            props, qrows = self._propose(eng, slot, seq, pos, k_eff)
        k1 = k_eff + 1
        ids = np.zeros((1, k1), np.int32)
        ids[0, 0] = seq.last_tok
        if k_eff:
            ids[0, 1:] = props
        vsp = None if h.trace is None else get_tracer().start_span(
            "engine.verify", h.trace, slot=slot, pos=pos,
        )
        flat_block, width = eng._spec_gather(slot, pos + k_eff)
        with profiler.RecordEvent(f"serving::spec_verify_w{width}"):
            logits, new_block = eng._run(
                ("spec_verify", width, k1), self._verify_fn(width, k1),
                eng._params, eng._buffers, jnp.asarray(ids), flat_block,
                jnp.int32(pos),
            )
        eng._spec_adopt(slot, new_block, width, pos)
        rows = np.asarray(logits, np.float32)
        if eng.do_sample:
            a, out = accept_sampled(
                rows, qrows, props, jnp.asarray(seq.key), pos,
                eng.temperature, eng.top_k, eng.top_p,
            )
        else:
            a, out = accept_greedy(rows, props)
        dt = eng.clock() - t0
        # bookkeeping BEFORE emission: _append may finish the request
        # (EOS / max_new) and retire the row under us
        self.rounds += 1
        self.proposed += k_eff
        self.accepted += a
        self.emitted += len(out)
        h.spec_rounds = getattr(h, "spec_rounds", 0) + 1
        h.spec_emitted = getattr(h, "spec_emitted", 0) + len(out)
        tid = None if h.trace is None else h.trace.trace_id
        m = eng.metrics
        m.spec_rounds.inc()
        m.spec_proposed.inc(k_eff)
        m.spec_accepted.inc(a)
        m.spec_accept_length.observe(len(out), trace_id=tid)
        # per-SLO-class child bound at admission (zero label work here)
        (seq.slo_itl or m.itl).observe(dt / len(out))
        if vsp is not None:
            vsp.finish(proposed=k_eff, accepted=a, emitted=len(out))
        for t in out:
            if eng._seqs[slot] is None:
                break  # EOS mid-burst: later tokens never happened
            eng._append(slot, int(t))
        if eng._seqs[slot] is not None:
            new_pos = eng._seqs[slot].pos
            # rejected-tail rollback: transient pages past the accepted
            # span go back to the pool; the draft rewinds to the
            # accepted prefix (its rejected-tail KV is masked until
            # overwritten next round)
            eng._spec_rollback(slot, new_pos)
            st = self._slots.get(slot)
            if st is not None and st.fed > new_pos:
                st.fed = new_pos
