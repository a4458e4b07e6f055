"""Builder ``dense_decoder``: maps a configuration file's published keys
onto the program's ``LlamaConfig`` / ``LlamaForCausalLM`` — the
repo's one decoder, which computes Mistral-7B and InternLM2-7B exactly
(no bias, no window in use, ``head_dim = hidden / heads``).

The net is built under ``paddle.LazyGuard()`` (parameters are shapes
only) and every parameter is then made on the device, in the cell's
dtype, in ONE jitted call from ``--seed``: normal with std 0.02, norm
weights one. That skips the float32 transient of 4 bytes a parameter
the plain constructor pays, and the host.
"""
from __future__ import annotations

import zlib

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "tie_word_embeddings")


def program_config(cfg):
    import paddle_tpu as paddle

    if cfg.get("head_dim") not in (None, cfg["hidden_size"]
                                   // cfg["num_attention_heads"]):
        raise ValueError("dense_decoder: head_dim must be hidden / heads")
    if cfg.get("sliding_window") is not None:
        raise ValueError("dense_decoder: no sliding window in this decoder")
    return paddle.models.LlamaConfig(**{k: cfg[k] for k in _KEYS if k in cfg})


def seeded_values(shapes, seed, dtype):
    """``{name: array}`` for ``{name: shape}``, one jitted call."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    def make(key):
        out = {}
        for n in names:
            if len(shapes[n]) == 1:     # RMSNorm weights
                out[n] = jnp.ones(shapes[n], dtype)
            else:
                k = jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF)
                out[n] = (0.02 * jax.random.normal(k, shapes[n], jnp.float32)
                          ).astype(dtype)
        return out

    return jax.jit(make)(jax.random.key(seed))


def build(cfg, seed, dtype):
    """The program's net for ``cfg`` with seeded weights of ``dtype``
    on the default device; returns ``(net, program_config)``."""
    import paddle_tpu as paddle

    pcfg = program_config(cfg)
    with paddle.LazyGuard():
        net = paddle.models.LlamaForCausalLM(pcfg)
    params = dict(net.named_parameters())
    values = seeded_values(
        {k: tuple(p.value.shape) for k, p in params.items()}, seed, dtype)
    for k, p in params.items():
        p.value = values[k]
    return net, pcfg


def build_hybrid(cfg, seed, parallel):
    """The Fleet hybrid path across chips, as ``chip_smoke.py
    --multichip`` sets it up: ``HybridCommunicateGroup`` over
    ``parallel`` (``{"dp": 2, "mp": 2}``), ``LlamaForCausalLMPipe`` with
    one stage under the default layout policy. Parameters are float32
    masters made by the program's own initializers from ``paddle.seed``
    (a training net pays no transient for them). Returns ``(net,
    place)``; ``place`` puts a [batch, seq] array on the mesh."""
    import jax
    from jax.sharding import NamedSharding

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology,
        HybridCommunicateGroup,
    )
    from paddle_tpu.parallel import layout

    axes = ["dp", "pp", "sharding", "sep", "mp"]
    hcg = HybridCommunicateGroup(CommunicateTopology(
        axes, [int(parallel.get(a, 1)) for a in axes]))
    paddle.seed(seed)
    net = paddle.models.LlamaForCausalLMPipe(program_config(cfg), num_stages=1)
    sharding = NamedSharding(hcg.mesh, layout.get_policy().batch_spec(2))
    return net, lambda a: jax.device_put(a, sharding)


def weights(net):
    """The net's current arrays by the single-model names the reference
    reads (a pipe net is renamed through its own ``to_causal_lm``)."""
    if hasattr(net, "to_causal_lm"):
        net = net.to_causal_lm()
    return {k: p.value for k, p in net.named_parameters()}
