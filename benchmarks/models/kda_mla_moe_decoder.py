"""Builder ``kda_mla_moe_decoder``: maps a configuration file's
published keys onto the program's ``KimiLinearConfig`` /
``KimiLinearForCausalLM`` (KDA gated-delta-rule layers whose state is
kept a row, every fourth layer NoPE latent attention over a one-array
page, a leading dense layer, sigmoid-routed experts of which a share is
held: ``paddle_tpu/models/kimi_linear.py``).

In the file ``num_experts`` counts the experts HELD here, from
``experts_first`` on; ``published.num_experts`` is the router's width.
The net is built under ``paddle.LazyGuard()`` and its parameters are
then made on the device from ``--seed`` exactly as builder
``linear_moe_decoder`` makes its own (``seeded_values``: one jitted
call a decoder layer; ``A_log`` and ``dt_bias`` the family's draws,
norm weights 1, the output gate's bias 0, every other matrix normal
with std 0.02). What is generic over a net that states its cache
(``served_path``: bucketed prefills adopted into pages AND rows, then
paged one-token steps) is that builder's too.
"""
from __future__ import annotations

from benchmarks.models.linear_moe_decoder import (  # noqa: F401
    seeded_values,
    served_path,
    weights,
)

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads",
         "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "mla_use_nope", "rope_scaling", "linear_attn_config",
         "num_experts_per_token", "num_shared_experts", "moe_renormalize",
         "routed_scaling_factor", "model_max_length", "rms_norm_eps",
         "tie_word_embeddings")


def program_config(cfg):
    import paddle_tpu as paddle

    if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError("kda_mla_moe_decoder: sigmoid routing only")
    if cfg.get("num_expert_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("kda_mla_moe_decoder: no group-limited routing")
    if cfg.get("moe_layer_freq", 1) != 1 \
            or cfg.get("num_nextn_predict_layers", 0):
        raise ValueError("kda_mla_moe_decoder: every layer after the dense "
                         "ones is an expert layer, and no MTP module")
    held = int(cfg["num_experts"])
    return paddle.models.KimiLinearConfig(
        num_experts=int(cfg.get("published", {}).get("num_experts", held)),
        experts_first=int(cfg.get("experts_first", 0)), experts_held=held,
        **{k: cfg[k] for k in _KEYS if k in cfg})


def _lazy_net(cfg):
    import paddle_tpu as paddle

    pcfg = program_config(cfg)
    with paddle.LazyGuard():
        return paddle.models.KimiLinearForCausalLM(pcfg), pcfg


def parameter_shapes(cfg):
    """``{name: shape}`` of the program's own parameters for ``cfg``,
    nothing allocated: what the counts file is held to."""
    return {k: tuple(p.value.shape)
            for k, p in _lazy_net(cfg)[0].named_parameters()}


def build(cfg, seed, dtype):
    """The program's net for ``cfg`` with seeded weights of ``dtype``
    on the default device; returns ``(net, program_config)``."""
    net, pcfg = _lazy_net(cfg)
    params = dict(net.named_parameters())
    values = seeded_values(
        {k: tuple(p.value.shape) for k, p in params.items()}, seed, dtype)
    for k, p in params.items():
        p.value = values[k]
    return net, pcfg


def expert_layer_outputs(net, inputs):
    """The program's expert FFN on GIVEN inputs: ``inputs`` is
    ``{decoder layer index: h [T, C]}`` over expert layers (each taken
    in the dtype the net is served in), and the layer's module runs its
    own ``forward`` (router over every expert, the sort, the grouped
    matmuls over the held share, combine, shared expert) and ``route``.
    Returns ``{index: (y [T, C] float32, chosen experts [T, k])}``. One
    compiled program for all layers: the first asked layer's module
    runs with the asked layer's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor

    mlps = [layer.mlp for layer in net.model.layers]
    first = mlps[min(inputs)]
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
    kda = next(i for i, rows in enumerate(generation.row_layout(cfg))
               if rows)

    @jax.jit
    def run(q, k, v, g, beta):
        # a KDA layer keeps no token array: its state is its first
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


def mla_step_outputs(net, engine, x, positions):
    """The program's MLA mixer alone on a GIVEN mixer input ``x [S,
    C]`` of one sequence, the way the served path runs it: the module's
    own forward over all of ``x`` with a block as its cache (K and V
    MATERIALISED from the latent, flash attention; the latent written
    into the block in the cache's type), the block adopted into pages,
    then ONE one-token step of ``len(positions)`` rows over a page
    table as wide as the engine's: row ``r`` is fed ``x[positions[r]]``
    at that position, writes its latent where the prefill's lies,
    gathers its pages through the span ladder and attends ABSORBED.
    Returns the step's output ``[rows, C]`` float32: the mixer's output
    at ``positions``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import generation
    from paddle_tpu.quantization.kv import adopt_into_pages

    cfg = net.config
    index = next(i for i in range(cfg.num_hidden_layers) if cfg.is_mla(i))
    mixer = net.model.layers[index].mixer
    own = {k: p.value for k, p in mixer.named_parameters()}
    s, ps = int(x.shape[0]), int(engine["page_size"])
    n_pages, rows = s // ps, len(positions)
    width = -(-int(engine["max_seq_len"]) // ps)
    table = np.zeros((rows, width), np.int32)
    table[:, :n_pages] = 1 + np.arange(n_pages)       # page 0 is garbage
    dtype = jnp.dtype(engine["cache_dtype"])
    trailing = generation.cache_layout(cfg)[index][0]

    @jax.jit
    def run(values, x, positions, table):
        mixer.load_functional_state(values, {})
        block = jnp.zeros((1, s) + trailing, dtype)
        with tape.trace_scope(), tape.no_grad():
            _, block = mixer(Tensor(x[None]), cache=block,
                             pos=jnp.int32(0))
            arena = adopt_into_pages(
                jnp.zeros((n_pages + 1, ps) + trailing, dtype), block,
                1 + jnp.arange(n_pages), n_pages, ps)
            out, _ = mixer(Tensor(x[positions][:, None]), cache=arena,
                           pos=positions, page_table=table)
        return out.value[:, 0].astype(jnp.float32)

    try:
        return np.asarray(run(
            own, jnp.asarray(x).astype(own["o_proj.weight"].dtype),
            jnp.asarray(positions, jnp.int32), jnp.asarray(table)))
    finally:
        mixer.load_functional_state(own, {})


def adopted_by_engine(net, engine, ids, plant=None):
    """What the ENGINE's own admission leaves a row, at the timed size:
    a ``PagedServingEngine`` built as the cell builds it admits ONE
    request whose prompt is ``ids`` (its own bucketed prefill program,
    its own page claim and its adoption program, ``adopt_state_body``:
    the block's latent scattered into pages and its state and tail
    copied into the row at once), and as that program returns, before a
    decode step has touched anything, the row's token arrays are read
    back THROUGH the row's own page table and its row arrays out of the
    row. ``plant(engine, arena, block, page_ids, row)`` (the controls
    tool's) rewrites what the adoption program is handed. Returns
    ``{layer index: tuple of float32 arrays}``: an MLA layer's latent
    ``[len(ids), cache_dim]``, a KDA layer's state ``[H, d, d]`` and
    its tail ``[K - 1, channels]``."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import generation
    from paddle_tpu.serving import PagedServingEngine

    cfg = net.config
    eng = PagedServingEngine(net, **engine)
    n_pages = -(-len(ids) // eng.page_size)
    kept = [(len(tokens), len(rows)) for tokens, rows in zip(
        generation.cache_layout(cfg), generation.row_layout(cfg))]
    seen, run = {}, eng._run

    def _run(key, fn, *args):
        if key[0] != "adopt":
            return run(key, fn, *args)
        row = int(args[3])
        pages = jnp.asarray(eng._tables[row, :n_pages])
        out = run(key, fn, *(args if plant is None else plant(eng, *args)))
        flat = iter(out)
        for index, (n_tokens, n_rows) in enumerate(kept):
            read = [np.asarray(next(flat)[pages].astype(jnp.float32))
                    for _ in range(n_tokens)]
            read = [a.reshape((-1,) + a.shape[2:])[:len(ids)] for a in read]
            read += [np.asarray(next(flat)[row].astype(jnp.float32))
                     for _ in range(n_rows)]
            seen[index] = tuple(read)
        return out

    eng._run = _run
    try:
        eng.generate([np.asarray(ids)], max_new_tokens=2)
    finally:
        eng.close()
    return seen
