"""Builder ``linear_moe_decoder``: maps a configuration file's published
keys onto the program's ``SolarOpen2Config`` / ``SolarOpen2ForCausalLM``
(KDA gated-delta-rule layers whose state is kept a row, every fourth
layer gated softmax attention without rope, sigmoid-routed experts of
which a share is held: ``paddle_tpu/models/solar_open2.py``).

In the file ``n_routed_experts`` counts the experts HELD here, from
``experts_first`` on; ``published.n_routed_experts`` is the router's
width. The net is built under ``paddle.LazyGuard()`` (parameters are
shapes only) and the parameters are then made on the device in the
cell's dtype from ``--seed``, one jitted call a decoder layer (layers
of one kind share a compiled program), so that the float32 transient of
the random draw is one layer's at most. What is drawn, by name (the
configuration file's ``assumed`` says why):

- ``*.A_log``: ``log U(1, 16)``; ``*.dt_bias``: ``softplus^-1(U(0.001,
  0.1))``;
- RMSNorm weights: 1; the output gate's bias: 0;
- every other matrix, the convolution's filters among them: normal,
  std 0.02.
"""
from __future__ import annotations

import functools
import re
import zlib

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "linear_attn_config", "gqa_interval", "use_rope", "use_gqa_gate",
         "kda_use_full_proj", "kda_allow_neg_eigval",
         "first_k_dense_replace", "n_shared_experts", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor",
         "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "tie_word_embeddings")
_LAYER = re.compile(r"^(model\.layers\.\d+\.)(.+)$")


def program_config(cfg):
    import paddle_tpu as paddle

    kw = {k: cfg[k] for k in _KEYS if k in cfg}
    held = int(cfg["n_routed_experts"])
    return paddle.models.SolarOpen2Config(
        n_routed_experts=int(cfg.get("published", {}).get(
            "n_routed_experts", held)),
        experts_first=int(cfg.get("experts_first", 0)), experts_held=held,
        gqa_layers=tuple(cfg["gqa_layers"]) if cfg.get("gqa_layers")
        is not None else None, **kw)


def _draw(name, shape, key, dtype):
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if name.endswith(".A_log"):
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if name.endswith(".dt_bias"):
        dt = jax.random.uniform(k, shape, jnp.float32, 0.001, 0.1)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name.endswith(".bias"):
        return jnp.zeros(shape, dtype)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)          # norm weights
    return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(spec, dtype):
    """One jitted ``key -> {name: array}`` for ``spec``, a tuple of
    ``(name, shape)``: layers of one kind share it."""
    import jax

    return jax.jit(lambda key: {n: _draw(n, s, key, dtype) for n, s in spec})


def seeded_values(shapes, seed, dtype):
    """``{name: array}`` for ``{name: shape}``: the parameters outside
    the layers in one call, then one call a layer."""
    import jax

    groups = {}
    for name in sorted(shapes):
        m = _LAYER.match(name)
        prefix, rel = (m.group(1), m.group(2)) if m else ("", name)
        groups.setdefault(prefix, []).append((rel, tuple(shapes[name])))
    root, out = jax.random.key(seed), {}
    for prefix, spec in groups.items():
        key = jax.random.fold_in(
            root, zlib.crc32(prefix.encode()) & 0x7FFFFFFF)
        made = _maker(tuple(spec), str(dtype))(key)
        out.update({prefix + n: v for n, v in made.items()})
    return out


def parameter_shapes(cfg):
    """``{name: shape}`` of the program's own parameters for ``cfg``,
    nothing allocated: what the counts file is held to."""
    import paddle_tpu as paddle

    with paddle.LazyGuard():
        net = paddle.models.SolarOpen2ForCausalLM(program_config(cfg))
    return {k: tuple(p.value.shape) for k, p in net.named_parameters()}


def build(cfg, seed, dtype):
    """The program's net for ``cfg`` with seeded weights of ``dtype``
    on the default device; returns ``(net, program_config)``."""
    import paddle_tpu as paddle

    pcfg = program_config(cfg)
    with paddle.LazyGuard():
        net = paddle.models.SolarOpen2ForCausalLM(pcfg)
    params = dict(net.named_parameters())
    values = seeded_values(
        {k: tuple(p.value.shape) for k, p in params.items()}, seed, dtype)
    for k, p in params.items():
        p.value = values[k]
    return net, pcfg


def weights(net):
    """The net's current arrays by the names the reference reads."""
    return {k: p.value for k, p in net.named_parameters()}


def served_path(net, engine, ids, lengths, steps):
    """The SERVED path's own logits and state, teacher-forced over the
    one sequence ``ids [bucket]``. The engine's programs end in the
    sampler and hand out tokens alone, so the same bodies are compiled
    here with the logits as their output, at the engine's sizes
    ``engine`` (the cell's: rows, table width, page size, cache dtype):

    - the bucketed prefill over a block (``generation.prefill``: flash
      attention without rope, the chunked KDA scan frozen at
      ``length``, the grouped matmuls at bucket x k rows, the head on
      one row), run once for each of ``lengths`` (one a decode row, one
      program);
    - the adoption of that block into the row's OWN pages and into the
      row's state and tail (``adopt_into_pages`` / ``adopt_into_slab``,
      as the engine's ``adopt_state_body``);
    - ``steps`` paged one-token steps over all rows together
      (``generation.decode_step`` with a page table: K and V written
      into the row's page, the span-ladder read, the one-token state
      update of every KDA layer): row ``r`` of step ``s`` is fed
      ``ids[lengths[r] + s]`` at that position and gives the logits of
      that position.

    Returns ``(prefill logits [rows, V] at positions lengths - 1,
    decode logits [steps, rows, V] at positions lengths + s, the row
    arrays a prefill of the WHOLE of ids leaves, the row arrays of row
    0 after its last step)``: logits float32, the row arrays flat (a
    KDA layer's state, then its tail) as they are kept."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.models import generation
    from paddle_tpu.quantization.kv import adopt_into_pages, adopt_into_slab

    cfg, values = net.config, weights(net)
    bucket, ps = len(ids), int(engine["page_size"])
    rows, dtype = int(engine["max_batch_size"]), engine["cache_dtype"]
    width = -(-int(engine["max_seq_len"]) // ps)
    n_pages = bucket // ps
    by_row = generation.row_array_mask(cfg)
    lengths = np.asarray(lengths, np.int32)
    if len(lengths) != rows or lengths.min() < 1 \
            or lengths.max() + steps > bucket:
        raise ValueError(f"{len(lengths)} prefill lengths up to "
                         f"{lengths.max()} + {steps} steps do not fit "
                         f"{rows} rows of a {bucket}-token sequence")
    table = 1 + np.arange(rows * width, dtype=np.int32).reshape(rows, width)

    def counted():                  # no tracer outlives its trace
        pop = getattr(net, "pop_step_counters", None)
        return pop() if pop is not None else {}

    def run_prefill(values, ids, length):
        net.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            logits, block = generation.prefill(
                net, ids[None], generation.alloc_kv_caches(
                    cfg, 1, bucket, dtype), length=length)
        counted()
        return logits[0].astype(jnp.float32), \
            [a for layer in block for a in layer]

    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill_into(values, ids, length, arena, page_ids, row):
        logits, block = run_prefill(values, ids, length)
        return logits, [
            adopt_into_slab(a, b, row) if is_row
            else adopt_into_pages(a, b, page_ids, n_pages, ps)
            for a, b, is_row in zip(arena, block, by_row)]

    @jax.jit
    def prefill_whole(values, ids):
        _, block = run_prefill(values, ids, jnp.int32(bucket))
        return [b for b, is_row in zip(block, by_row) if is_row]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(values, arena, tok, pos, table):
        net.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            logits, caches = generation.decode_step(
                net, tok[:, None], generation.unflatten_caches(arena, cfg),
                pos, page_table=table)
        counted()
        return logits.astype(jnp.float32), \
            [a for layer in caches for a in layer]

    ids = jnp.asarray(ids, jnp.int32)
    arena = [a for layer in generation.alloc_kv_caches(
        cfg, rows * width + 1, ps, dtype, rows=rows) for a in layer]
    try:
        first = []
        for r, length in enumerate(lengths):
            logits, arena = prefill_into(
                values, ids, jnp.int32(length), arena,
                jnp.asarray(table[r, :n_pages]), jnp.int32(r))
            first.append(np.asarray(logits))
        out = []
        for s in range(steps):
            pos = jnp.asarray(lengths + s)
            logits, arena = step(values, arena, ids[pos], pos,
                                 jnp.asarray(table))
            out.append(np.asarray(logits))
        stepped = [np.asarray(a[0]) for a, is_row in zip(arena, by_row)
                   if is_row]
        del arena
        whole = [np.asarray(a[0]) for a in prefill_whole(values, ids)]
    finally:
        net.load_functional_state(values, {})   # tracing left tracers
    return np.stack(first), np.stack(out), whole, stepped


def expert_layer_outputs(net, inputs):
    """The program's expert FFN on GIVEN inputs: ``inputs`` is
    ``{decoder layer index: h [T, C]}`` (each taken in the dtype the
    net is served in), and the layer's module runs its own ``forward``
    (router over every expert, the sort, the grouped matmuls over the
    held share, combine, shared expert) and ``route``. Returns ``{index:
    (y [T, C] float32, chosen experts [T, k])}``. One compiled program
    for all layers: the first layer's module runs with the asked
    layer's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor

    mlps = [layer.mlp for layer in net.model.layers]
    first = mlps[0]
    own = {k: p.value for k, p in first.named_parameters()}

    @jax.jit
    def run(values, h):
        first.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            h = Tensor(h)
            idx, _ = first.route(h)
            y = first(h).value
        first.last_counts = None
        return y.astype(jnp.float32), idx

    # read before the first trace parks its tracers on ``first``
    asked = {index: {k: p.value
                     for k, p in mlps[index].named_parameters()}
             for index in inputs}
    out = {}
    try:
        for index, h in inputs.items():
            y, idx = run(asked[index], jnp.asarray(h).astype(
                own["gate_weight"].dtype))
            out[index] = (np.asarray(y), np.asarray(idx))
    finally:
        first.load_functional_state(own, {})
    return out


def kda_kernel_state(net, fed, steps, cache_dtype):
    """The program's two state kernels alone, at the timed size, on
    GIVEN float32 inputs ``fed = (q, k, v, g, beta)`` of one sequence
    (``[S, H, d]``, ``beta`` ``[S, H]``): the chunked scan over ``S -
    steps`` tokens from a zero state, then ``steps`` one-token updates,
    the state kept between the calls in the array the net's row
    statement allocates for it (``generation.alloc_kv_caches``: its
    type is part of what is held). Returns the final state ``[H, d,
    d]`` float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import generation, solar_open2

    cfg = net.config
    kda = next(i for i in range(cfg.num_hidden_layers) if not cfg.is_gqa(i))

    @jax.jit
    def run(q, k, v, g, beta):
        kept = generation.alloc_kv_caches(cfg, 1, 1, cache_dtype)[kda][0]
        n = q.shape[0] - steps
        head = lambda a: a[None, :n]
        _, state = solar_open2.kda_scan(
            head(q), head(k), head(v), head(g), head(beta), kept,
            cfg.kda_chunk)
        kept = state.astype(kept.dtype)
        for t in range(n, n + steps):
            _, state = solar_open2.kda_step(
                q[None, t], k[None, t], v[None, t], g[None, t],
                beta[None, t], kept)
            kept = state.astype(kept.dtype)
        return kept[0].astype(jnp.float32)

    return np.asarray(run(*(jnp.asarray(a, jnp.float32) for a in fed)))
