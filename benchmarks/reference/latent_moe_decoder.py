"""Plain reference of the latent-attention expert decoder
(Xing4.0-29B-A4B: MLA, sigmoid-routed experts, residual streams mixed
by manifold-constrained hyper-connections, an MTP module).

The forward pass in straightforward ``jax.numpy``: float32, every
matmul at ``precision="highest"``, no cache, no kernels, no absorbed
attention, no grouped matmul: K and V are made from the latent for
every position, attention runs a head at a time (so that the scores of
a 4224-token check are 71 MB and not 2.3 GB), and the experts are a
plain loop over all of them, each applied to every token and weighted
by the router's weight for it (zero where the token did not choose
it). It takes the net's own weights as a ``{name: array}`` dict,
whatever type they are served in; each is raised to float32 as it is
used, a layer (an expert) at a time, so the reference never holds a
float32 copy of the model beside the served one.

Equations, on ``X [S, n, C]`` (``n = hc_mult`` residual streams)::

    X0    = Emb(ids) replicated over the n streams
    sub-layer, for F in (attention, ffn), each with its own phi, b, a:
      xf    = vec(X) * rsqrt(mean(vec(X)^2) + hc_eps)           # [n C]
      z     = xf phi                                    # [n + n + n n]
      Hpre  = sigmoid(a0 z_pre + b_pre);  Hpost = 2 sigmoid(a1 z_post + b_post)
      M     = exp(clip(a2 mat(z_res) + b_res, clamp_min, clamp_max))
      hc_sinkhorn_iters times: M = M / (rowsum + hc_eps);
                               M = M / (colsum + hc_eps)
      X     = M X + Hpost[:, None] * F(RMSNorm_w(Hpre X))
    attention(h):
      cq = RMSNorm_w(h Wqa); [q_nope | q_rope] = cq Wqb        # H x (dn | dr)
      [ckv | k_rope] = h Wkva; ckv = RMSNorm_w(ckv)
      [k_nope | v] = ckv Wkvb                                   # H x (dn | dv)
      q_rope, k_rope = rope(.), rotate-half, YaRN frequencies
      a = softmax((q_nope.k_nope + q_rope.k_rope) * scale + causal) v
      scale = (dn + dr)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2
      out = concat_heads(a) Wo
    ffn(h), expert layers:
      s = sigmoid(h Wg); chosen = top-k of (s + e_bias)
      w = s[chosen] / (sum + 1e-20) * routed_scaling_factor
      y = sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)
    logits = RMSNorm_w(sum over streams of X) W_head
    MTP: h' = [RMSNorm_w(h_main_i) ; RMSNorm_w(Emb(t_{i+1}))] W_eh, one
      expert layer on its own streams (positions 0..), own final norm,
      the same head; h_main is the stream sum before the final norm.

Departures and assumptions (the configuration file's ``assumed`` has
the reasons): gate and up projections are stored side by side (first
half gate), the 64 experts of a layer stacked in one array; linear
weights are ``[in, out]``; how the streams start and end, the Sinkhorn
order and where ``hc_eps`` enters, and the rope pairing are not in the
published config and are chosen as written above.

Tolerances. Three comparisons decide ``correct`` in this model's cell;
the limits are below, each beside its readings on the chip:

- the served tokens (``jobs/serve.py``'s check, ``SERVE_LOGIT_GAP`` and
  ``SERVE_MEAN_GAP``): for every served token, the top reference logit
  at its position minus the reference logit of the served token; the
  largest and the mean over the check's 4 x 32 tokens. It drives the
  engine's own programs through the front end (bucketed prefill, adopt,
  page growth, paged absorbed decode) and holds them against what is
  WRONG: a lost page, a mask off by one, a cache in another layout;
- the served path's logits (``jobs/serve_latent_moe.py``'s
  ``check_path``, ``PATH_ERR``): the same bodies as the engine's
  programs, compiled with the logits as output and teacher-forced
  (``models/latent_moe_decoder.served_path_logits``), against this
  reference at the 256 positions of one 4096-token sequence where the
  reference's OWN routing is most decided (``decisive_rows``), a
  relative error a position (``relative_logit_errors``), the 90th
  percentile over the positions; the same limit holds the median over
  eight prefills' own rows. It holds the PRECISION of the served path:
  the cache, the attention, the mixing, the head;
- the expert layers alone (``check_path`` too, ``FFN_ERR`` and
  ``ROUTE_ELSEWHERE``): the program's expert FFN module on this
  reference's own FFN inputs (rounded to the served type) against
  ``expert_ffn`` on the same inputs, 1024 tokens a layer: the relative
  error a token, its 90th percentile, and the share of tokens sent to
  another set of experts. It holds the experts' and the router's
  precision where the end-to-end numbers cannot.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The configuration's own noise (TPU v5e, my chip runs, PR 28; PERF.md
# section 6 has the seeds). With 64 experts, top-4 and random weights
# the last chosen and the first unchosen biased score lie 0.013 apart
# (median), and the bf16 rounding of the hidden state moves them:
# 3.4-3.9 / 5.8-6.7 / 8.3-9.2 / 11.9-13.0 / 15.0-16.0 % of the tokens of
# the five expert layers go to another set of experts than in float32
# (five seeds), about 38 % of the positions have such a flip in some
# layer, and the logits at a position flipped early are up to 0.7 of
# the logits' spread away from the reference's. A flipped served token
# reads 0.14-0.30 below the reference's top on average and up to 4.09,
# an unflipped one 0.008-0.03. In 128 served tokens that is larger than
# what a lower precision adds (max / mean over three seeds: as served
# 1.19-2.54 / 0.040-0.082, int8 experts 1.21-1.89 / 0.049-0.067, fp8
# (e4m3) experts 1.05-1.67 / 0.067-0.097).
#
# So the served-token limits hold the engine's programs against what is
# WRONG, not against a lower precision; the limits below them do that.
# Readings: as served, largest 0.58-4.09 and mean 0.026-0.128 over
# twenty-two seeds; tokens drawn at random (a stream that is garbage)
# read 1.4-8.7 each, 5.2-5.5 on average, the largest of 128 8.1-8.7
# (five seeds, tools/latent_moe_precision.py). The mean's limit lies
# between the largest sound reading (x 2.3) and 0.36, what one garbage
# token in sixteen reads (a page boundary lost: 5.2 / 16 + 0.04). The
# largest gap's limit lies between 4.09 (x 1.7) and 8.1 (x 1.16), what
# a stream garbage throughout reads: a single garbage token and a
# single flip's token read alike, so it can refuse only such a stream,
# which the mean refuses sooner. Job serve reads both names.
SERVE_LOGIT_GAP = 7.0
SERVE_MEAN_GAP = 0.3
# ||served path - reference|| / ||reference - its mean|| a position, at
# the 256 positions whose least margin over the five expert layers is
# largest (0.0099-0.0110 and up, against 0.0025-0.0028 at the median
# position): NONE of them went to another expert set in any layer, in
# any of five seeds (the tool's line "routing"). Their 90th percentile:
# as the configuration states it 0.0154-0.0177 over twelve seeds (median
# 0.0133-0.0142, largest 0.018-0.023); the routed experts' weights
# through fp8 (e4m3) 0.0410-0.0477; the prefilled latent pages through
# fp8 0.137-0.209; the experts through int8, a scale an output channel,
# 0.0192-0.0218 (five seeds each, tools/latent_moe_precision.py). The
# limit lies between the first and the fp8 experts' (x 1.36 above the
# one, x 1.71 below the other). int8 experts move the whole path's
# logits by a quarter (the bf16 activations' own rounding is 0.0135 of
# the spread, int8 weights add 0.010 in quadrature): no end-to-end
# number parts them widely, so they are held where they are alone
# (FFN_ERR). The same limit holds the median over the eight prefills'
# own rows: 0.0117-0.0136 as stated, 0.0302-0.0390 with fp8 experts.
PATH_ERR = 0.024
# ||program's expert FFN - reference's|| / ||reference's|| a token, on
# the reference's own FFN inputs, 5 x 1024 tokens, 90th percentile: as
# stated 0.00369-0.00370 over twelve seeds (the grouped matmuls' bf16
# roundings); int8 experts 0.01172-0.01177; fp8 experts 0.0349-0.0350;
# a router that keeps logits and scores in bfloat16 0.0038. The limit is
# the geometric mean of the first two: x 1.78 on both sides.
FFN_ERR = 0.0066
# Share of those tokens (the reference's margin on that input above
# 1e-4: float32 against float32 cannot part there) whose chosen set
# differs: as stated 0 of 5120, twelve seeds; a router that keeps its
# logits and scores in bfloat16 (each rounded by reduce_precision; as
# casts the chip computes it in float32 all the same) 0.0294-0.0298,
# two seeds: NOT correct, by this limit alone. int8 and fp8 experts
# read 0 here, by FFN_ERR they fail.
ROUTE_ELSEWHERE = 0.005
# ... counted over the tokens whose margin in the reference, on that
# input, is above this: float32's own noise in a sigmoid score is 1e-6
ROUTE_DECIDED = 1e-4

_HI = "highest"
_F32 = jnp.float32


def _mm(a, w):
    return jnp.matmul(a, w.astype(_F32), precision=_HI)


def _rms(x, w, eps):
    x = x.astype(_F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * w.astype(_F32)


def _swiglu(h, w_gate_up, w_down):
    gu = _mm(h, w_gate_up)
    ffn = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:], w_down)


# ------------------------------------------------------------------ rope
def yarn(cfg):
    """``(inv_freq [dr / 2], attention scale)`` of the config's rope
    scaling, as DeepSeek-V3 computes YaRN."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    qk = cfg["qk_nope_head_dim"] + dim
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc or sc.get("factor", 1) <= 1:
        return plain.astype(np.float32), qk ** -0.5
    factor = float(sc["factor"])
    orig = float(sc["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(sc["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = plain / factor * ramp + plain * (1 - ramp)
    m = 0.1 * float(sc.get("mscale_all_dim", 0) or 0) * math.log(factor) + 1
    return inv.astype(np.float32), qk ** -0.5 * m * m


def _rope(x, inv):
    """x [S, ..., d]: rotate-half with positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    f = jnp.arange(s, dtype=_F32)[:, None] * jnp.asarray(inv)[None, :]
    f = f.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(f), jnp.sin(f)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------- pieces
def hc_maps(x, phi, bias, alpha, *, iters, eps, lo, hi):
    """``x [S, n, C]`` -> ``Hpre [S, n]``, ``Hpost [S, n]``, ``Hres
    [S, n, n]``."""
    s, n, _ = x.shape
    z = _mm(_rms(x.reshape(s, -1), None, eps), phi)
    a, b = alpha.astype(_F32), bias.astype(_F32)
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:], lo, hi)
                ).reshape(s, n, n)
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return h_pre, h_post, m


def attention(h, wqa, nqa, wqb, wkva, nkva, wkvb, wo, *, heads, dn, dr, dv,
              eps, inv, scale):
    s = h.shape[0]
    kvl = wkvb.shape[0]
    q = _mm(_rms(_mm(h, wqa), nqa, eps), wqb).reshape(s, heads, dn + dr)
    kva = _mm(h, wkva)
    kv = _mm(_rms(kva[:, :kvl], nkva, eps), wkvb).reshape(s, heads, dn + dv)
    k_rope = _rope(kva[:, kvl:], inv)                          # [S, dr]
    q_rope = _rope(q[..., dn:], inv)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(args):
        qn, qr, kn, v = args                                   # [S, .]
        sc = (jnp.matmul(qn, kn.T, precision=_HI)
              + jnp.matmul(qr, k_rope.T, precision=_HI)) * scale
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.matmul(p, v, precision=_HI)

    per_head = lambda a: jnp.swapaxes(a, 0, 1)                 # [H, S, .]
    out = jax.lax.map(one_head, (per_head(q[..., :dn]), per_head(q_rope),
                                 per_head(kv[..., :dn]),
                                 per_head(kv[..., dn:])))
    return _mm(per_head(out).reshape(s, heads * dv), wo)


def route(h, w_gate, e_bias, *, top_k, scale, renorm):
    """Weights ``[S, E]`` (zero where an expert was not chosen), the
    chosen experts ``[S, k]`` and the margin between the last chosen
    and the first unchosen biased score ``[S]``."""
    s = jax.nn.sigmoid(_mm(h, w_gate))
    biased, idx = jax.lax.top_k(s + e_bias.astype(_F32), top_k + 1)
    margin = biased[:, top_k - 1] - biased[:, top_k]
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(s, idx, -1)
    if renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx] \
        .set(w * scale)
    return dense, idx, margin


def experts(h, weights, w_gate_up, w_down):
    """``sum_e weights[:, e] * SwiGLU_e(h)``: a plain loop over every
    expert, one raised to float32 at a time."""
    def one(acc, xs):
        col, gu, dn = xs
        return acc + col[:, None] * _swiglu(h, gu, dn), None

    acc, _ = jax.lax.scan(one, jnp.zeros(h.shape, _F32),
                          (weights.T.astype(_F32), w_gate_up, w_down))
    return acc


def _expert_ffn(h, w, prefix, moe):
    dense, chosen, margin = route(h, w[prefix + "gate_weight"],
                                  w[prefix + "e_bias"], **moe)
    y = experts(h, dense, w[prefix + "experts_gate_up"],
                w[prefix + "experts_down"]) \
        + _swiglu(h, w[prefix + "shared_expert.gate_up_proj.weight"],
                  w[prefix + "shared_expert.down_proj.weight"])
    return y, chosen, margin


@functools.partial(jax.jit, static_argnames=("moe",))
def expert_ffn(h, w, *, moe):
    """The expert FFN alone (router, every expert, the shared one) on a
    GIVEN input ``h [T, C]``; ``w`` the ``mlp.`` weights of one expert
    layer by their names relative to it. Returns ``(y [T, C], chosen
    [T, k], margin [T])``: what the program's own expert layer is held
    to on the same input."""
    return _expert_ffn(h.astype(_F32), w, "", dict(moe))


_STATIC = ("heads", "dn", "dr", "dv", "eps", "inv", "scale", "hc", "moe")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(x, w, *, heads, dn, dr, dv, eps, inv, scale, hc, moe):
    """One decoder layer on ``x [S, n, C]``; ``w`` the layer's weights
    by their names relative to it. Returns ``(x, chosen experts,
    margin, the expert FFN's input [S, C])``, the last three None for
    a dense layer."""
    hc = dict(hc)
    inv = np.asarray(inv, np.float32)

    def mix(x, prefix, f):
        h_pre, h_post, h_res = hc_maps(
            x, w[prefix + "phi"], w[prefix + "bias"], w[prefix + "alpha"],
            **hc)
        y = f(jnp.einsum("sn,snc->sc", h_pre, x, precision=_HI))
        return jnp.einsum("sij,sjc->sic", h_res, x, precision=_HI) \
            + h_post[:, :, None] * y[:, None, :]

    a = "self_attn."
    x = mix(x, "attn_hc.", lambda h: attention(
        _rms(h, w["input_layernorm.weight"], eps),
        w[a + "q_a_proj.weight"], w[a + "q_a_layernorm.weight"],
        w[a + "q_b_proj.weight"], w[a + "kv_a_proj.weight"],
        w[a + "kv_a_layernorm.weight"], w[a + "kv_b_proj.weight"],
        w[a + "o_proj.weight"], heads=heads, dn=dn, dr=dr, dv=dv, eps=eps,
        inv=inv, scale=scale))
    chosen = margin = ffn_in = None

    def ffn(h):
        nonlocal chosen, margin, ffn_in
        h = _rms(h, w["post_attention_layernorm.weight"], eps)
        if moe is None:
            return _swiglu(h, w["mlp.gate_up_proj.weight"],
                           w["mlp.down_proj.weight"])
        ffn_in = h
        y, chosen, margin = _expert_ffn(h, w, "mlp.", dict(moe))
        return y

    x = mix(x, "ffn_hc.", ffn)
    return x, chosen, margin, ffn_in


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, w_norm, w_head, *, eps):
    return _mm(_rms(h, w_norm, eps), w_head)


def _static(cfg):
    inv, scale = yarn(cfg)
    return dict(
        heads=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        eps=float(cfg["rms_norm_eps"]), inv=tuple(float(f) for f in inv),
        scale=float(scale),
        hc=(("iters", int(cfg["hc_sinkhorn_iters"])),
            ("eps", float(cfg["hc_eps"])),
            ("lo", float(cfg["mhc_h_res_clamp_min"])),
            ("hi", float(cfg["mhc_h_res_clamp_max"]))))


def moe_static(cfg):
    """The routing constants of ``cfg``, as ``layer`` and ``expert_ffn``
    take them (``moe=``)."""
    return (("top_k", int(cfg["num_experts_per_tok"])),
            ("scale", float(cfg["routed_scaling_factor"])),
            ("renorm", bool(cfg.get("norm_topk_prob", True))))


def _layer_weights(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(weights, cfg, ids, routing=None):
    """The stream sum after the last layer ``[S, C]`` (before the final
    norm) of one sequence ``ids [S]``. ``routing``, a list, gets every
    expert layer's ``(chosen [S, k], margin [S], FFN input [S, C])``."""
    emb = jnp.take(weights["model.embed_tokens.weight"], ids, axis=0) \
        .astype(_F32)
    x = jnp.broadcast_to(emb[:, None, :],
                         (emb.shape[0], cfg["hc_mult"], emb.shape[1]))
    st = _static(cfg)
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        x, *routed = layer(
            x, _layer_weights(weights, f"model.layers.{i}."), **st,
            moe=None if dense else moe_static(cfg))
        if routing is not None and not dense:
            routing.append(tuple(routed))
    return jnp.sum(x, axis=1)


def logits(weights, cfg, ids, rows=None, routing=None):
    """Float32 logits of one sequence ``ids [S]``: ``[S, vocab]``, or
    only at the positions ``rows``."""
    h = hidden(weights, cfg, ids, routing)
    if rows is not None:
        h = h[rows]
    return head(h, weights["model.norm.weight"], weights["lm_head.weight"],
                eps=float(cfg["rms_norm_eps"]))


def mtp_logits(weights, cfg, ids):
    """Logits ``[S - 1, vocab]`` of the multi-token-prediction module:
    row ``i`` joins the main model's hidden state at ``i`` with token
    ``i + 1``."""
    eps = float(cfg["rms_norm_eps"])
    h = hidden(weights, cfg, ids)[:-1]
    e = jnp.take(weights["model.embed_tokens.weight"], ids[1:], axis=0) \
        .astype(_F32)
    x = _mm(jnp.concatenate([_rms(h, weights["mtp.hnorm.weight"], eps),
                             _rms(e, weights["mtp.enorm.weight"], eps)], -1),
            weights["mtp.eh_proj.weight"])
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"],
                                         x.shape[1]))
    x = layer(x, _layer_weights(weights, "mtp.block."), **_static(cfg),
              moe=moe_static(cfg))[0]
    return head(jnp.sum(x, 1), weights["mtp.norm.weight"],
                weights["lm_head.weight"], eps=eps)


def relative_logit_errors(got, want):
    """``||got - want|| / ||want - mean(want)||`` a position, for
    logits ``[rows, vocab]``: the program's error as a share of the
    spread of the reference's logits there."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    spread = want - want.mean(-1, keepdims=True)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(spread, axis=-1)


def relative_errors(got, want):
    """``||got - want|| / ||want||`` a row."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


def expert_layers(cfg):
    return list(range(cfg["first_k_dense_replace"],
                      cfg["num_hidden_layers"]))


def least_margins(routing):
    """``[S]``: a position's smallest margin over the expert layers,
    the distance by which the reference's own routing there is
    decided."""
    return np.min(np.stack([np.asarray(r[1]) for r in routing]), axis=0)


def decisive_rows(margins, lo, hi, n):
    """The ``n`` positions of ``[lo, hi)`` whose least margin is
    largest, in rising order: where the configuration's own precision
    cannot send a token to another expert, by the reference's numbers
    alone."""
    m = np.asarray(margins)[lo:hi]
    return np.sort(lo + np.argsort(-m, kind="stable")[:n]).astype(np.int32)


def served_token_gaps(weights, cfg, prompt, served, pad_to):
    """For each served token: top reference logit at its position minus
    the reference logit of the served token (>= 0). One forward over
    prompt + served tokens, padded on the right to ``pad_to`` (causal:
    padding cannot reach earlier positions) so every check compiles one
    shape; the head runs on the served positions alone."""
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p] = prompt
    ids[n_p:n_p + n_s] = served
    lg = logits(weights, cfg, jnp.asarray(ids),
                rows=jnp.arange(n_p - 1, n_p - 1 + n_s))
    got = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(served, np.int32))[:, None], 1)[:, 0]
    return np.asarray(jnp.max(lg, -1) - got, np.float32)
