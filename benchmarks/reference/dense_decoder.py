"""Plain reference of a dense pre-norm decoder (Mistral-7B, InternLM2-7B).

The forward pass and the causal-LM loss in straightforward
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching tricks. It takes the net's own weights
as a ``{name: array}`` dict (whatever type they are served in; each is
raised to float32 as it is used, one layer at a time, so the reference
never holds a float32 copy of the model beside the served one).

Equations, per layer, on ``x [S, hidden]``::

    h  = x * rsqrt(mean(x^2) + eps) * w_in
    q, k, v = h Wq, h Wk, h Wv            # heads x d, kv_heads x d
    q, k = rope(q), rope(k)               # rotate-half, base rope_theta
    a  = softmax(q k^T / sqrt(d) + causal) v   # k, v repeated per group
    x  = x + a Wo
    h  = x * rsqrt(mean(x^2) + eps) * w_post
    x  = x + (silu(h Wg) * (h Wu)) Wd

then the final norm and the head. Departures from the published
models, none of which changes the mathematics: the program stores
``Wg`` and ``Wu`` side by side as one ``gate_up_proj`` [hidden, 2*ffn]
(first half gate) and InternLM2 stores q/k/v fused as ``wqkv``: both
are storage layouts of the same projections. InternLM2's dynamic-NTK
rope scaling acts only beyond 32768 positions and is not modelled.
Linear weights are [in, out].

Tolerances, with the gaps measured on the chip (TPU v5e, my chip runs,
PR 24) beside them:

- ``TRAIN_LOSS_RTOL`` 2e-4: the trainer's first-step loss (AMP O2: bf16
  operands, fp32 accumulation and master weights) against this loss on
  the same batch and weights. Measured over seven seeds at Mistral
  widths, depth 3, 4096 tokens: relative difference 1.8e-6 to 3.6e-5.
- ``SERVE_LOGIT_GAP`` 0.35 and ``SERVE_MEAN_GAP`` 0.015: for every
  served token, the top reference logit at its position minus the
  reference logit of the served token; the largest and the mean over
  the check's 4 x 32 tokens. Logits and not token equality: with random
  weights (logit scale 1.3, top logit about 5.4) the top two tie inside
  one bf16 ulp and the rounding order decides. Measured over seven
  seeds, depth 8, bf16 weights and KV: largest gap 0.082 to 0.144
  (InternLM2) and 0.085 to 0.138 (Mistral), mean gap 0.0020 to 0.0057.
  The mean is the sharper test: a path that rounds weights or
  activations more coarsely than bf16 moves every position, not a tie
  or two. It was not measured against an int8 or fp8 path here; the
  cell that serves one (PERF.md section 7) sets its own tolerance and
  must show that it fails this one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TRAIN_LOSS_RTOL = 2e-4
SERVE_LOGIT_GAP = 0.35
SERVE_MEAN_GAP = 0.015

_HI = "highest"


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x [S, heads, d]: rotate-half with positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, w):
    return jnp.matmul(a, w.astype(jnp.float32), precision=_HI)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def layer(x, w_in, wq, wk, wv, wo, w_post, w_gate_up, w_down, *, heads,
          kv_heads, eps, theta):
    s = x.shape[0]
    d = wq.shape[1] // heads
    h = _rms(x, w_in, eps)
    q = _rope(_mm(h, wq).reshape(s, heads, d), theta)
    k = _rope(_mm(h, wk).reshape(s, kv_heads, d), theta)
    v = _mm(h, wv).reshape(s, kv_heads, d)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / d ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(s, -1)
    x = x + _mm(a, wo)
    h = _rms(x, w_post, eps)
    gu = _mm(h, w_gate_up)
    ffn = gu.shape[-1] // 2
    return x + _mm(jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:], w_down)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, w_norm, w_head, *, eps):
    return _mm(_rms(x, w_norm, eps), w_head)


def logits(weights, cfg, ids):
    """Float32 logits [S, vocab] of one sequence ``ids [S]``."""
    g = weights.__getitem__
    x = jnp.take(g("model.embed_tokens.weight"), ids, axis=0) \
        .astype(jnp.float32)
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg.get("num_key_value_heads")
              or cfg["num_attention_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        x = layer(x, g(p + "input_layernorm.weight"),
                  g(p + "self_attn.q_proj.weight"),
                  g(p + "self_attn.k_proj.weight"),
                  g(p + "self_attn.v_proj.weight"),
                  g(p + "self_attn.o_proj.weight"),
                  g(p + "post_attention_layernorm.weight"),
                  g(p + "mlp.gate_up_proj.weight"),
                  g(p + "mlp.down_proj.weight"), **kw)
    w_head = (weights["lm_head.weight"] if "lm_head.weight" in weights
              else g("model.embed_tokens.weight").T)
    return head(x, g("model.norm.weight"), w_head, eps=kw["eps"])


@jax.jit
def _nll(lg, labels):
    lp = jax.nn.log_softmax(lg, -1)
    return -jnp.take_along_axis(lp, labels[:, None], 1)[:, 0]


def loss(weights, cfg, inputs, labels):
    """Causal-LM loss: mean cross-entropy of ``logits[b, t]`` against
    ``labels[b, t]`` (the token after ``inputs[b, t]``) over every
    position, one sequence at a time."""
    rows = [_nll(logits(weights, cfg, x), y) for x, y in zip(inputs, labels)]
    return float(jnp.mean(jnp.stack(rows)))


def served_token_gaps(weights, cfg, prompt, served, pad_to):
    """For each served token: top reference logit at its position minus
    the reference logit of the served token (>= 0). One forward over
    prompt + served tokens, padded on the right to ``pad_to`` (causal:
    padding cannot reach earlier positions) so every check compiles one
    shape."""
    import numpy as np

    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p] = prompt
    ids[n_p:n_p + n_s] = served
    lg = logits(weights, cfg, jnp.asarray(ids))[n_p - 1:n_p - 1 + n_s]
    top = jnp.max(lg, -1)
    got = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(served, np.int32))[:, None], 1)[:, 0]
    return np.asarray(top - got, np.float32)
