"""Autoregressive decoding for the Llama family — the TPU way.

Reference parity: PaddleNLP's ``model.generate`` (greedy/sampling
decode strategies over a KV cache — unverified, mount empty).

TPU-first design: the ENTIRE generate — prefill plus every decode step
— is one jitted program. The KV cache is a static [B, S_max, kvH, D]
buffer per layer written with ``dynamic_update_slice``; the decode loop
is a ``lax.scan`` over ``max_new_tokens`` with the caches in the carry.
No growing tensors, no per-token dispatch: one compile per
(batch, prompt_len, max_new_tokens) signature, then every token is a
single fused device step. Finished sequences (EOS seen) keep emitting
``eos_token_id`` — the standard static-shape treatment.
"""
from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp

from ..core import tape
from ..core.tensor import Tensor


def filter_logits(logits, temperature, top_k, top_p):
    """The sampling head's distribution shaping, factored out so the
    speculative acceptance math uses the IDENTICAL filtered logits the
    compiled decode programs sample from: [B, V] float -> fp32 [B, V]
    with temperature applied and non-nucleus entries at -inf."""
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        # clamp: top_k >= vocab keeps every token (reference generate
        # semantics) instead of an out-of-bounds sort index at trace time
        top_k = min(int(top_k), int(logits.shape[-1]))
        kth = jnp.sort(scaled, axis=-1)[:, -int(top_k)][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p is not None and top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # with cumulative probability >= top_p (the kept set always
        # includes the most-probable token)
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p  # token enters before mass reached p
        # the argmax token always survives (top_p -> 0 must collapse to
        # greedy, not to an all-masked distribution emitting token 0)
        keep = keep.at[:, 0].set(True)
        cutoff = jnp.where(keep, srt, jnp.inf).min(axis=-1, keepdims=True)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return scaled


def _select_next(logits, do_sample, temperature, top_k, top_p, key):
    """logits [B, V] -> next token ids [B]. ``key`` is one key [2] for
    the whole batch (generate()'s per-step chain) or a per-row [B, 2]
    key array (the serving engines' per-request position-folded keys —
    each row samples from its own stream)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits, temperature, top_k, top_p)
    if getattr(key, "ndim", 1) == 2:
        return jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(key, scaled).astype(jnp.int32)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


DEFAULT_CACHE_DTYPE = "bfloat16"

# the full set of KV-cache storage dtypes the decode paths implement:
# fp32 (bit-exact parity with the cacheless forward), bf16 (the serving
# default), int8 (quantized storage + per-token scales — see
# quantization/kv.py). Anything else fails HERE, at the API seam, with
# the allowed set — not deep inside jnp after the cache is allocated.
ALLOWED_CACHE_DTYPES = ("float32", "bfloat16", "int8")


def normalize_cache_dtype(cache_dtype):
    """Validate a ``cache_dtype`` knob value -> canonical dtype name.
    ``None`` means the default. Raises ValueError naming the allowed
    set for anything the cache paths do not implement."""
    if cache_dtype is None:
        return DEFAULT_CACHE_DTYPE
    try:
        name = jnp.dtype(cache_dtype).name
    except TypeError:
        raise ValueError(
            f"unknown cache_dtype {cache_dtype!r}; allowed: "
            f"{ALLOWED_CACHE_DTYPES}"
        ) from None
    if name not in ALLOWED_CACHE_DTYPES:
        raise ValueError(
            f"cache_dtype {cache_dtype!r} is not a supported KV-cache "
            f"storage dtype; allowed: {ALLOWED_CACHE_DTYPES}"
        )
    return name

# monotonic per-net token for trace-guard keys: id(net) would be reused
# after GC, merging a dead net's compile history (and _fired state) into
# a new net's
_NET_GUARD_IDS = itertools.count()


def cache_layout(cfg):
    """What one cached token is made of, layer by layer: for each layer
    a tuple with the TRAILING shape of every cache array it keeps (a
    cache array is ``[B, S_max, *trailing]``, a page arena
    ``[pages, page_size, *trailing]``). A config that keeps anything
    but a K and a V of ``(kvH, D)`` a layer states it
    (``cfg.cache_layout()``: a latent-attention net ONE array of
    ``(latent + rope dims,)`` shared by all heads); one that states
    nothing (Llama, every decoder before it) keeps the pair, and this
    is where the pair is written down."""
    stated = getattr(cfg, "cache_layout", None)
    if stated is None:
        pair = ((int(cfg.kv_heads), int(cfg.head_dim)),) * 2
        return [pair] * int(cfg.num_hidden_layers)
    return [tuple(tuple(int(d) for d in a) for a in layer)
            for layer in stated()]


def row_layout(cfg):
    """What one ROW keeps whatever its length, layer by layer: for each
    layer a tuple of ``(shape, dtype)``, one for every array addressed
    by row and not by token (a row array is ``[rows, *shape]``, in
    ``alloc_kv_caches``, in a block and beside a page arena alike;
    dtype None is the cache's). A config with a recurrent layer states
    it (``cfg.row_layout()``: the layer's state and its convolution
    tail); one that states nothing keeps nothing a row."""
    stated = getattr(cfg, "row_layout", None)
    if stated is None:
        return [()] * len(cache_layout(cfg))
    return [tuple((tuple(int(d) for d in shape), dtype)
                  for shape, dtype in layer) for layer in stated()]


def keeps_row_state(cfg):
    """True where some layer keeps an array a row (``row_layout``)."""
    return any(row_layout(cfg))


def row_array_mask(cfg):
    """For the flat list of a net's cache arrays (``[a for layer in
    caches for a in layer]``): True where the array is addressed by
    row, False by token."""
    return [is_row for tokens, rows in zip(cache_layout(cfg),
                                           row_layout(cfg))
            for is_row in (False,) * len(tokens) + (True,) * len(rows)]


def token_arrays_are_kv_pairs(cfg):
    """True where every layer that keeps anything a TOKEN keeps a K and
    a V of ``(kvH, D)`` (a layer may keep nothing a token: a recurrent
    one)."""
    return all(
        len(layer) == 2 and len(layer[0]) == 2 and layer[0] == layer[1]
        for layer in cache_layout(cfg) if layer)


def keeps_kv_pairs(cfg):
    """True where every layer's cache is a K and a V of ``(kvH, D)``
    and nothing a row: the layout int8 storage, the prefix cache,
    tiering and speculation are written for."""
    return not keeps_row_state(cfg) and all(cache_layout(cfg)) \
        and token_arrays_are_kv_pairs(cfg)


def cache_token_bytes(cfg, cache_dtype):
    """Bytes ONE cached token costs over every layer's arrays (int8
    counts its per-token fp32 scales)."""
    from ..quantization.kv import kv_token_bytes

    return sum(kv_token_bytes(math.prod(a[:-1]), a[-1], cache_dtype)
               for layer in cache_layout(cfg) for a in layer)


def cache_row_bytes(cfg, cache_dtype):
    """Bytes ONE row keeps over every layer's row arrays, whatever its
    length (0 for a net that states none)."""
    default = jnp.dtype(normalize_cache_dtype(cache_dtype))
    return sum(math.prod(shape) * jnp.dtype(dtype or default).itemsize
               for layer in row_layout(cfg) for shape, dtype in layer)


def unflatten_caches(flat, cfg):
    """The per-layer tuples of a flat list of cache arrays (the
    inverse of ``[a for layer in caches for a in layer]``): a layer's
    token arrays, then its row arrays."""
    out, i = [], 0
    for tokens, rows in zip(cache_layout(cfg), row_layout(cfg)):
        n = len(tokens) + len(rows)
        out.append(tuple(flat[i:i + n]))
        i += n
    return out


def alloc_kv_caches(cfg, B, S_max, cache_dtype=None, rows=None):
    """Per-layer static cache buffers ``[B, S_max, *trailing]``, one
    for each array ``cache_layout(cfg)`` states (Llama: K and V,
    ``[B, S_max, kvH, D]`` x2 a layer), and behind them in the layer's
    tuple ``[rows, *shape]`` for each array ``row_layout(cfg)`` states
    (``rows`` defaults to ``B``; a page arena, whose ``B`` counts
    pages, says how many rows it serves).

    ONE place owns the serving cache layout and dtype: the whole-decode
    programs here, the serving engine's slot slab, and the bucketed
    ``serving.kv_pool`` blocks all allocate through this (bf16 default —
    halves decode HBM vs the old unconditional fp32; the attention path
    upcasts to the compute dtype at the matmul). ``"int8"`` allocates
    quantized storage (int8 values + per-token fp32 scales as one
    :class:`~..quantization.kv.QuantizedKV` pytree per array — halves
    resident bytes again; the write paths quantize, the reads
    dequantize); it exists for K/V pairs only."""
    name = normalize_cache_dtype(cache_dtype)
    layout = cache_layout(cfg)
    if name == "int8":
        if not keeps_kv_pairs(cfg):
            raise ValueError(
                "int8 cache storage quantizes K and V per head; "
                f"{type(cfg).__name__} keeps another cache layout"
            )
        from ..quantization.kv import alloc_quantized

        return [
            tuple(alloc_quantized((B, S_max) + a) for a in layer)
            for layer in layout
        ]
    dtype = jnp.dtype(name)
    rows = B if rows is None else int(rows)
    return [
        tuple(jnp.zeros((B, S_max) + a, dtype) for a in layer)
        + tuple(jnp.zeros((rows,) + shape, jnp.dtype(kept or dtype))
                for shape, kept in row_layer)
        for layer, row_layer in zip(layout, row_layout(cfg))
    ]


def prefill(net, ids, caches, length=None, pos=0):
    """Run the prompt through the cache path in one pass (caches filled
    [pos, pos + S)). ``ids`` may be right-padded to a bucket length:
    pass ``length`` (scalar, traceable) and the returned logits row is
    taken at position ``length - 1`` instead of the last column.
    Bucketed prefill is numerically exact for both kinds of cache: in
    an array addressed by TOKEN pad tokens only ever write slots that
    decode overwrites before reading (causal masking); an array
    addressed by ROW (a recurrent state, ``row_layout``) has no such
    slots, so a net that keeps one is handed ``length`` and freezes its
    row arrays past it.

    ``pos`` (scalar, traceable; default 0) starts the chunk at an
    offset: tokens land at cache positions [pos, pos + S) and attend to
    everything already cached below ``pos`` — the CHUNKED prefill the
    serving prefix cache uses to recompute only the uncached tail of a
    prompt (tier-1-pinned bitwise-equal to the full-prompt prefill).
    Returns (next-token logits [B, V], caches)."""
    # a net that says so (``head_takes_row``) runs its head on the one
    # row wanted. Kept for the FIT alone: a 4096 x 131072 block of
    # logits is 1.07 GB beside a model that fills the chip. Every
    # causal LM should get the one-row head and this attribute go
    # (ROADMAP A9, a perf_opt PR of its own with the Llama cells
    # measured)
    one_row = length is not None and getattr(net, "head_takes_row", False)
    kw = {"head_row": jnp.asarray(length, jnp.int32) - 1} if one_row else {}
    if length is not None and keeps_row_state(net.config):
        kw["length"] = jnp.asarray(length, jnp.int32)
    with tape.trace_scope(), tape.no_grad():
        logits, caches = net(
            Tensor(ids), caches=caches, pos=jnp.asarray(pos, jnp.int32),
            **kw
        )
    lv = logits.value
    if one_row:
        return lv[:, 0, :], caches
    if length is None:
        return lv[:, -1, :], caches
    row = jax.lax.dynamic_index_in_dim(
        lv, jnp.asarray(length, jnp.int32) - 1, axis=1, keepdims=False
    )
    return row, caches


def decode_step(net, tok, caches, pos, page_table=None):
    """One KV-cache decode step — the reusable hot-loop body shared by
    the whole-decode scan below and ``serving.ServingEngine``'s compiled
    step program. ``tok`` [B, 1] int32; ``pos`` is a scalar (whole-batch
    decode) or an int32 [B] vector (continuous batching: every row sits
    at its own depth). With ``page_table`` ([B, P] int32) the caches are
    per-layer PAGE ARENAS and attention runs through the table — the
    paged serving engine's step. Cache-dtype-aware: writes cast to the
    cache's dtype, reads upcast at the matmul. Returns
    (logits [B, V], caches)."""
    # the kwarg is forwarded only when paging: the slab and the
    # whole-decode paths call a net's cache seam without it, so a net
    # with no paged path of its own still decodes through them
    kw = {} if page_table is None else {"page_table": page_table}
    with tape.trace_scope(), tape.no_grad():
        logits, caches = net(Tensor(tok), caches=caches, pos=pos, **kw)
    return logits.value[:, -1, :], caches


def _alloc_and_prefill(net, ids, S_max, cache_dtype=None):
    """Allocate the per-layer static KV buffers and run the prompt
    through in one pass (caches filled [0, S_prompt)). Shared by the
    greedy/sampling and beam decode bodies — ONE place owns the cache
    layout. Returns (last-position logits [B, V], caches)."""
    caches = alloc_kv_caches(net.config, ids.shape[0], S_max, cache_dtype)
    return prefill(net, ids, caches)


def _decode_ids(net, ids, max_new, do_sample, top_k, top_p, has_eos,
                temperature, eos_id, key, cache_dtype=None):
    """The traced decode body (prefill + scan); callable from both the
    generate() jit and the exportable GreedyDecoder layer. ``ids`` is a
    jnp [B, S_prompt] int array; returns jnp [B, S_prompt + max_new]."""
    cfg = net.config
    B, S_prompt = ids.shape[0], ids.shape[1]  # no int(): jnp accepts dims
    S_max = S_prompt + max_new
    logits, caches = _alloc_and_prefill(net, ids, S_max, cache_dtype)
    if do_sample:  # greedy never reads the key: keep it out of the
        key, sub = jax.random.split(key)  # program entirely (smaller
    else:  # exported StableHLO, no per-token threefry work)
        sub = key
    next_tok = _select_next(logits, do_sample, temperature, top_k,
                            top_p, sub)
    finished = (
        (next_tok == eos_id) if has_eos
        else jnp.zeros((B,), bool)
    )
    flat = [a for kv in caches for a in kv]

    def step(carry, _):
        tok, pos, flat, finished, key = carry
        caches = unflatten_caches(flat, cfg)
        logits, caches = decode_step(net, tok[:, None], caches, pos)
        if do_sample:
            key, sub = jax.random.split(key)
        else:
            sub = key
        nxt = _select_next(logits, do_sample, temperature, top_k,
                           top_p, sub)
        if has_eos:
            nxt = jnp.where(finished, eos_id, nxt)
            finished = finished | (nxt == eos_id)
        flat = [a for kv in caches for a in kv]
        return (nxt, pos + 1, flat, finished, key), nxt

    (_, _, _, _, _), toks = jax.lax.scan(
        step,
        (next_tok, jnp.int32(S_prompt), flat, finished, key),
        None, length=max_new - 1,
    ) if max_new > 1 else ((None,) * 5, jnp.zeros(
        (0, B), jnp.int32
    ))
    return jnp.concatenate(
        [ids.astype(jnp.int32), next_tok[:, None],
         jnp.swapaxes(toks, 0, 1)], axis=1,
    )


def _beam_decode_ids(net, ids, max_new, num_beams, has_eos, eos_id,
                     cache_dtype=None):
    """Beam search with the beams folded into the batch dim ([B*k] rows
    share one compiled program with everything else): each step scores
    [B, k*V], takes the top k continuations, and GATHERS the KV caches
    by surviving-beam index inside the scan. A finished beam is frozen
    (EOS emits with logprob 0, everything else -inf) so its score stays
    comparable. Returns the best beam per batch, [B, S_prompt+max_new].
    """
    cfg = net.config
    B, S_prompt = ids.shape[0], ids.shape[1]
    k = num_beams
    S_max = S_prompt + max_new
    NEG = jnp.float32(-1e30)

    logits, caches = _alloc_and_prefill(net, ids, S_max, cache_dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)  # [B,V]
    V = logp.shape[-1]
    # first expansion: top-k tokens per batch seed the beams
    scores, tok0 = jax.lax.top_k(logp, k)  # [B, k]
    finished = (
        (tok0 == eos_id) if has_eos else jnp.zeros((B, k), bool)
    )
    # beams share the prompt cache: tile to [B*k]
    flat = [
        jnp.repeat(a, k, axis=0) for kv in caches for a in kv
    ]
    # fixed-size token buffer (scan carries cannot grow): column t holds
    # generation step t, written via dynamic_update_slice
    beam_toks = jnp.zeros((B, k, max_new), jnp.int32).at[:, :, 0].set(
        tok0.astype(jnp.int32)
    )

    def step(carry, _):
        scores, beam_toks, flat, finished, pos = carry
        col = pos - S_prompt  # previous step's column
        tok = jax.lax.dynamic_slice_in_dim(
            beam_toks, col, 1, axis=2
        )[..., 0].reshape(B * k)
        caches = unflatten_caches(flat, cfg)
        logits, caches = decode_step(net, tok[:, None], caches, pos)
        lp = jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1
        ).reshape(B, k, V)
        if has_eos:
            # frozen beams: only EOS continues, at no cost
            frozen = jnp.full((V,), NEG).at[eos_id].set(0.0)
            lp = jnp.where(finished[..., None], frozen[None, None, :], lp)
        total = scores[..., None] + lp  # [B, k, V]
        scores2, idx = jax.lax.top_k(total.reshape(B, k * V), k)
        src_beam = idx // V  # [B, k] which beam each winner extends
        tok2 = (idx % V).astype(jnp.int32)
        # reorder everything by surviving beam
        gather = jnp.take_along_axis
        beam_toks2 = gather(
            beam_toks, src_beam[..., None], axis=1
        )
        z = jnp.zeros((), col.dtype)
        beam_toks2 = jax.lax.dynamic_update_slice(
            beam_toks2, tok2[..., None], (z, z, col + 1)
        )
        finished2 = gather(finished, src_beam, axis=1) if has_eos else (
            finished
        )
        if has_eos:
            finished2 = finished2 | (tok2 == eos_id)
        # global row index of each surviving beam's cache — gathered
        # from the POST-write caches (they hold this step's k/v)
        written = [a for kv in caches for a in kv]
        rows = (
            jnp.arange(B)[:, None] * k + src_beam
        ).reshape(B * k)
        flat2 = [a[rows] for a in written]
        return (scores2, beam_toks2, flat2, finished2, pos + 1), None

    if max_new > 1:
        (scores, beam_toks, _, _, _), _ = jax.lax.scan(
            step,
            (scores, beam_toks, flat, finished, jnp.int32(S_prompt)),
            None, length=max_new - 1,
        )
    # lax.top_k keeps beams sorted by score descending at every step,
    # so beam 0 IS the best beam
    chosen = beam_toks[:, 0, :]
    return jnp.concatenate(
        [ids.astype(jnp.int32), chosen.astype(jnp.int32)], axis=1
    )


def _build_decode(net, B, S_prompt, max_new, do_sample, top_k,
                  top_p, has_eos, num_beams=1,
                  cache_dtype=DEFAULT_CACHE_DTYPE):
    """Whole-generate program for one shape signature. The compiled fn
    is cached ON the net (``net._generate_cache``) so its lifetime is
    the model's — no module-global registry pinning dropped models
    alive. Weights enter as arguments, so updated weights do NOT need
    a recompile."""

    def run(params, buffers, ids, temperature, eos_id, key):
        net.load_functional_state(params, buffers)
        net.eval()
        if num_beams > 1:
            return _beam_decode_ids(net, ids, max_new, num_beams,
                                    has_eos, eos_id,
                                    cache_dtype=cache_dtype)
        return _decode_ids(net, ids, max_new, do_sample, top_k, top_p,
                           has_eos, temperature, eos_id, key,
                           cache_dtype=cache_dtype)

    return jax.jit(run)


def _make_greedy_mod():
    from .. import nn

    class _GreedyMod(nn.Layer):
        """forward(ids) -> full decoded ids; see GreedyDecoder."""

        def __init__(self, net, max_new, eos, num_beams=1,
                     cache_dtype=DEFAULT_CACHE_DTYPE):
            super().__init__()
            self.net = net
            self.max_new = max_new
            self.eos = eos
            self.num_beams = num_beams
            self.cache_dtype = cache_dtype
            # export must not flip the wrapped model's mode: jit.save
            # restores the OWNER's (this wrapper's) training flag onto
            # the whole tree afterwards, so mirror the net's mode here
            if net.training:
                self.train()
            else:
                self.eval()

        def forward(self, ids):
            v = ids.value if isinstance(ids, Tensor) else jnp.asarray(ids)
            eos = jnp.int32(self.eos if self.eos is not None else -1)
            if self.num_beams > 1:
                out = _beam_decode_ids(
                    self.net, v, self.max_new, self.num_beams,
                    self.eos is not None, eos,
                    cache_dtype=self.cache_dtype,
                )
            else:
                out = _decode_ids(
                    self.net, v, self.max_new, False, 0, 1.0,
                    self.eos is not None, jnp.float32(1.0), eos,
                    jax.random.PRNGKey(0),
                    cache_dtype=self.cache_dtype,
                )
            return Tensor(out)

    return _GreedyMod


class GreedyDecoder:
    """Exportable greedy decode head: ``forward(ids) -> ids + new``.

    Wraps a LlamaForCausalLM so the WHOLE decode (prefill + KV-cache
    scan) exports through ``paddle.jit.save`` as one StableHLO program
    and serves through ``inference.create_predictor`` — the deploy
    chain for generation. Greedy or deterministic beam search
    (``num_beams > 1``) — both RNG-free, so artifacts are
    deployment-deterministic. Decode programs are shape-specialized:
    export with a concrete [B, S_prompt] InputSpec.
    """

    def __init__(self, net, max_new_tokens, eos_token_id=None,
                 num_beams=1, cache_dtype=DEFAULT_CACHE_DTYPE):
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.layer = _make_greedy_mod()(
            net, int(max_new_tokens), eos_token_id, int(num_beams),
            normalize_cache_dtype(cache_dtype),
        )

    def save(self, path, input_spec):
        from ..jit.api import save as jit_save

        for s in input_spec or []:
            shape = getattr(s, "shape", None) or []
            if any(d is None or (isinstance(d, int) and d < 0)
                   for d in shape):
                raise ValueError(
                    "GreedyDecoder.save: decode programs are "
                    "shape-specialized (the KV cache and scan length "
                    "derive from the prompt shape) — provide a concrete "
                    f"[B, S_prompt] InputSpec, got {shape}"
                )
        jit_save(self.layer, path, input_spec=input_spec)


def generate(net, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             seed=0, num_beams=1, cache_dtype=DEFAULT_CACHE_DTYPE):
    """Greedy / top-k/top-p sampling / beam-search decode.
    Returns Tensor [B, S + new].

    ``cache_dtype``: KV-cache storage dtype (default bf16 — half the
    decode HBM of fp32; attention upcasts at the matmul). Pass
    ``"float32"`` for bit-exact parity with the cacheless forward."""
    ids = input_ids.value if isinstance(input_ids, Tensor) else jnp.asarray(
        input_ids
    )
    B, S = int(ids.shape[0]), int(ids.shape[1])
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if num_beams > 1 and do_sample:
        raise ValueError(
            "num_beams > 1 is deterministic beam search; combine with "
            "do_sample=False (sampled beam search is not implemented)"
        )
    cache_dtype = normalize_cache_dtype(cache_dtype)
    cache = net.__dict__.setdefault("_generate_cache", {})
    if num_beams > 1:
        # sampling knobs are ignored by the beam program: normalize them
        # out of the compile key so irrelevant differences don't force a
        # recompile of a byte-identical whole-decode program
        sig = (B, S, int(max_new_tokens), False, 0, 1.0,
               eos_token_id is not None, int(num_beams), cache_dtype)
    else:
        sig = (B, S, int(max_new_tokens), bool(do_sample), int(top_k),
               float(top_p) if top_p is not None else 1.0,
               eos_token_id is not None, 1, cache_dtype)
    fn = cache.get(sig)
    if fn is None:
        fn = cache[sig] = _build_decode(net, *sig)
        # compile-cache miss: every distinct (B, S, max_new, ...)
        # signature is a full whole-decode recompile — report it so the
        # analysis trace guard can flag callers whose prompt shapes
        # drift (the hazard serving's bucketing exists to prevent).
        # Keyed per net INSTANCE: several nets of one class each
        # legitimately compile a few programs; only one net's cache
        # growing unbounded is a storm.
        from ..analysis import trace_guard

        token = net.__dict__.setdefault(
            "_generate_guard_id", next(_NET_GUARD_IDS)
        )
        trace_guard.record_compile(
            f"generate::{type(net).__name__}#{token}", sig,
            origin="models/generation.py",
        )
    params = {k: p.value for k, p in net.named_parameters()}
    buffers = {k: b.value for k, b in net.named_buffers()}
    was_training = net.training
    try:
        out = fn(
            params, buffers, ids, jnp.float32(temperature),
            jnp.int32(eos_token_id if eos_token_id is not None else -1),
            jax.random.PRNGKey(seed),
        )
    finally:
        # tracing swapped tracers into the imperative Layer objects;
        # restore the concrete weights (CompiledTrainStep's write-back
        # pattern) and the caller's train/eval mode
        net.load_functional_state(params, buffers)
        if was_training:
            net.train()
        else:
            net.eval()
    # unified telemetry: offline generate() emits through the same
    # registry the serving engine and train step publish into (tokens
    # are the CAPACITY decoded — [B, max_new] slots; EOS-finished rows
    # pad to shape, the host can't see per-row stop depth without a sync)
    try:
        from ..observability import get_registry

        get_registry().counter(
            "paddle_generation_tokens_total",
            help="decode-slot tokens produced by models.generate "
                 "(batch * max_new_tokens per call)",
        ).inc(B * int(max_new_tokens),
              mode="beam" if num_beams > 1 else
              ("sample" if do_sample else "greedy"))
        get_registry().counter(
            "paddle_generation_calls_total",
            help="models.generate invocations",
        ).inc()
    except Exception:
        pass
    return Tensor(out)
