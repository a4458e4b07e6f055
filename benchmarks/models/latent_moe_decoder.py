"""Builder ``latent_moe_decoder``: maps a configuration file's published
keys onto the program's ``Xing4Config`` / ``Xing4ForCausalLM`` (latent
attention, sigmoid-routed experts run dropless, residual streams mixed
by mHC: ``paddle_tpu/models/xing4.py``).

The net is built under ``paddle.LazyGuard()`` (parameters are shapes
only) and the parameters are then made on the device in the cell's
dtype from ``--seed``, one jitted call a decoder layer (the expert
layers share one compiled program) so that the float32 transient of the
random draw is one layer's at most. What is drawn, by name (the
configuration file's ``assumed`` says why):

- ``*.phi``: normal, std ``(hc_mult * hidden_size)^-0.5``;
- ``*.e_bias``: normal, std 0.023;
- RMSNorm weights and the mHC gates ``alpha``: 1; the mHC ``bias``: 0;
- every other matrix: normal, std 0.02.
"""
from __future__ import annotations

import functools
import re
import zlib

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads",
         "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob", "hc_mult",
         "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
         "mhc_h_res_clamp_max", "num_nextn_predict_layers",
         "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "rope_scaling", "tie_word_embeddings")
E_BIAS_STD = 0.023
_LAYER = re.compile(r"^(model\.layers\.\d+\.|mtp\.)(.+)$")


def program_config(cfg):
    import paddle_tpu as paddle

    if cfg.get("scoring_func", "sigmoid") != "sigmoid" \
            or cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("latent_moe_decoder: sigmoid noaux_tc routing only")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("latent_moe_decoder: no group-limited routing")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("latent_moe_decoder: every layer after the dense "
                         "ones is an expert layer")
    return paddle.models.Xing4Config(**{k: cfg[k] for k in _KEYS if k in cfg})


def _draw(name, shape, key, dtype, phi_std):
    import jax
    import jax.numpy as jnp

    if name.endswith(".bias"):                 # the mHC maps' bias
        return jnp.zeros(shape, dtype)
    if len(shape) == 1 and not name.endswith(".e_bias"):
        return jnp.ones(shape, dtype)          # norm weights, mHC gates
    std = (phi_std if name.endswith(".phi")
           else E_BIAS_STD if name.endswith(".e_bias") else 0.02)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(spec, dtype, phi_std):
    """One jitted ``key -> {name: array}`` for ``spec``, a tuple of
    ``(name, shape)``: layers of one kind share it."""
    import jax

    return jax.jit(lambda key: {n: _draw(n, s, key, dtype, phi_std)
                                for n, s in spec})


def seeded_values(shapes, seed, dtype, phi_std):
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
        made = _maker(tuple(spec), str(dtype), float(phi_std))(key)
        out.update({prefix + n: v for n, v in made.items()})
    return out


def build(cfg, seed, dtype):
    """The program's net for ``cfg`` with seeded weights of ``dtype``
    on the default device; returns ``(net, program_config)``."""
    import paddle_tpu as paddle

    pcfg = program_config(cfg)
    with paddle.LazyGuard():
        net = paddle.models.Xing4ForCausalLM(pcfg)
    params = dict(net.named_parameters())
    values = seeded_values(
        {k: tuple(p.value.shape) for k, p in params.items()}, seed, dtype,
        (pcfg.hc_mult * pcfg.hidden_size) ** -0.5)
    for k, p in params.items():
        p.value = values[k]
    return net, pcfg


def weights(net):
    """The net's current arrays by the names the reference reads."""
    return {k: p.value for k, p in net.named_parameters()}


def served_path_logits(net, engine, ids, lengths, positions):
    """The SERVED path's own logits, teacher-forced. The engine's
    programs end in the sampler and hand out tokens alone, so the same
    bodies are compiled here with the logits as their output: the
    bucketed prefill over a block (``generation.prefill``: flash
    attention over the materialised K and V, the grouped matmuls at
    bucket x k rows, the head on one row), the adopt into pages
    (``adopt_into_pages``), and the paged one-token step
    (``generation.decode_step`` with a page table: the latent written
    into its page, the page gather, absorbed attention, the grouped
    matmuls at rows x k) at the engine's sizes ``engine`` (the cell's:
    rows, table width, page size, cache dtype).

    ``ids [bucket]`` is prefilled whole, once for each of ``lengths``
    (one program): a prefill's logits are those of row ``length - 1``.
    Every row of a decode step then reads the SAME pages (the last
    prefill's) and stands at a position of its own: row ``r`` of step
    ``s`` is fed ``ids[positions[s, r]]`` at that position, writes its
    latent where the prefill's lies, attends over the pages up to it,
    and gives the logits of that position. Returns ``(prefill logits
    [len(lengths), V], decode logits [steps, rows, V])``, float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.models import generation
    from paddle_tpu.quantization.kv import adopt_into_pages

    cfg, values = net.config, weights(net)
    bucket, ps = len(ids), int(engine["page_size"])
    rows, dtype = int(engine["max_batch_size"]), engine["cache_dtype"]
    n_pages = bucket // ps
    positions = np.asarray(positions, np.int32).reshape(-1, rows)
    table = np.zeros((rows, -(-int(engine["max_seq_len"]) // ps)), np.int32)
    table[:, :n_pages] = 1 + np.arange(n_pages)     # page 0 is garbage

    def counted():                  # no tracer outlives its trace
        pop = getattr(net, "pop_step_counters", None)
        return pop() if pop is not None else {}

    @jax.jit
    def prefill(values, ids, length):
        net.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            logits, block = generation.prefill(
                net, ids[None], generation.alloc_kv_caches(
                    cfg, 1, bucket, dtype), length=length)
        counted()
        arena = generation.alloc_kv_caches(cfg, n_pages + 1, ps, dtype)
        page_ids = 1 + jnp.arange(n_pages)
        arena = [tuple(adopt_into_pages(a, b, page_ids, n_pages, ps)
                       for a, b in zip(la, lb))
                 for la, lb in zip(arena, block)]
        return logits[0].astype(jnp.float32), arena

    @jax.jit
    def step(values, arena, tok, pos, table):
        net.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            logits, arena = generation.decode_step(
                net, tok[:, None], arena, pos, page_table=table)
        counted()
        return logits.astype(jnp.float32), arena

    ids = jnp.asarray(ids, jnp.int32)
    try:
        first = []
        for length in lengths:
            logits, arena = prefill(values, ids, jnp.int32(length))
            first.append(np.asarray(logits))
        out = []
        for pos in positions:
            pos = jnp.asarray(pos)
            logits, arena = step(values, arena, ids[pos], pos,
                                 jnp.asarray(table))
            out.append(np.asarray(logits))
    finally:
        net.load_functional_state(values, {})   # tracing left tracers
    return np.stack(first), np.stack(out)


def expert_layer_outputs(net, inputs):
    """The program's expert FFN on GIVEN inputs: ``inputs`` is
    ``{decoder layer index: h [T, C]}`` (each taken in the dtype the
    net is served in), and the layer's module runs its own ``forward``
    (router, sort, grouped matmuls, combine, shared expert) and
    ``route``. Returns ``{index: (y [T, C] float32, chosen experts
    [T, k])}``. One compiled program for all layers: the first expert
    layer's module runs with the asked layer's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import tape
    from paddle_tpu.core.tensor import Tensor

    mlps = [layer.mlp for layer in net.model.layers]
    first = mlps[net.config.first_k_dense_replace]
    own = {k: p.value for k, p in first.named_parameters()}

    @jax.jit
    def run(values, h):
        first.load_functional_state(values, {})
        with tape.trace_scope(), tape.no_grad():
            h = Tensor(h)
            idx, _ = first.route(h)
            y = first(h).value
        first.last_touched = None
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
