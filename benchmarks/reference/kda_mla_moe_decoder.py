"""Plain reference of the KDA / NoPE-MLA expert decoder
(Kimi-Linear-48B-A3B: three layers in four KDA gated-delta-rule, every
fourth latent attention without rope and without query compression, a
leading dense layer, sigmoid-routed experts of which this program may
hold a share).

The forward pass in straightforward ``jax.numpy``: float32, every
matmul at ``precision="highest"``, no cache, no chunked scan, no
absorbed attention, no grouped matmul, no batching. KDA is the
recurrence as written, a ``lax.scan`` over the tokens; MLA makes K and
V per head from the latent for every position and runs a head at a
time, ``ATTN_BLOCK`` queries at a time (scores ``[block, S]``: 32 x 16 k
x 16 k in float32 would be 34 GB whole); the experts are a plain loop
over the HELD ones, each applied to the rows that chose it (a gather a
held expert, the rows found by a stable sort of the chosen mask; every
row through every held expert would be 1e14 operations a layer at 16 k
tokens in six-pass float32). Computed a layer at a time from the net's
own weights (``{name: array}``, whatever type they are served in),
each raised to float32 as it is used, so the reference never holds a
float32 copy of the model beside the served one. The plain pieces this
family shares with Solar-Open2 (the KDA recurrence and its inputs, the
router, SwiGLU, the norms, the error measures) are imported from that
family's reference; nothing is imported from ``paddle_tpu/models``.

Equations, on ``x [S, C]``, eps ``rms_norm_eps``; layers are numbered
from 1 in ``linear_attn_config`` as published::

    x <- x + Mixer(RMSNorm_w(x));  x <- x + FFN(RMSNorm_w(x))
    KDA mixer (kda_layers), H heads of d_k = d_v = d:
      [q~ | k~ | v~] = x W_qkv
      q', k', v' = SiLU(sum_{j<K} c_j * (.)_{t-K+1+j})    # zeros before 0
      q = q' / sqrt(|q'|^2 + 1e-6) * d^-0.5;  k likewise, unscaled;  v = v'
      g = -exp(A_log_h) * softplus(x W_f1 W_f2 + dt_bias);  a = exp(g)
      beta = sigmoid(x W_b)
      S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T;   S_{-1} = 0
      o_t = S_t^T q_t
      y = [RMSNorm_w(o) * sigmoid(x W_g1 W_g2 + b_g)] W_o
    MLA mixer (full_attn_layers), H heads, NO rope, no q compression:
      q = x W_q                                   # H x (dn + dr)
      [ckv | k_pe] = x W_kva;  ckv = RMSNorm_w(ckv)
      [k_nope | v] = ckv W_kvb                    # H x (dn | dv)
      k = [k_nope | k_pe]                         # k_pe shared by the heads
      o = causal softmax(q k^T (dn + dr)^-0.5) v;  y = o W_o
    FFN, layers below first_k_dense_replace: SwiGLU of intermediate_size
    FFN, the others: s = sigmoid(x W_r)           # all num_experts
      chosen = top-k of s;  w = s[chosen] / (sum + 1e-20) * scaling
      y = sum_{e chosen AND held} w_e SwiGLU_e(x) + SwiGLU_shared(x)
    logits = RMSNorm_w(x) W_head

The expert share is ``(first, held)``: experts ``[first, first +
held)`` are held; what the absent ones would add is left out, and that
partial sum goes on to the next layer, in the program alike.

Departures and assumptions (the configuration file's ``assumed`` has
the reasons): ``beta`` without the factor 2; the gates' low-rank width
is ``d``; q~, k~, v~ from one matrix and the filters the same way; gate
and up of an expert side by side, the held experts stacked; linear
weights ``[in, out]``; the selection bias is zero and the one expert
group limits nothing; the latent is stored 640 wide, 576 of it used.
One comparison, the absorbed one-token step's (``MLA_STEP_ERR``), and
the latent the engine's admission is held to (``mla_latent``) take the
query and the latent ROUNDED where a deployment in the served and the
cache's type rounds them (``nope_mla(rounded=)``); the forward pass
(``hidden``, ``logits``) never does.

Tolerances. The served tokens (job ``serve``) and the readings of
``jobs/serve_kda_mla_moe.py``'s ``check_path`` decide ``correct`` in
this model's cell; each limit stands below beside its readings on the
chip and the reason for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.linear_moe_decoder import (  # noqa: F401
    ROUTE_DECIDED,                  # margins under 1e-4 are not counted
    _mm,
    _rms,
    _swiglu,
    head,
    kda_inputs_and_state,
    kda_mixer,
    kda_recurrence,
    relative_errors,
    relative_logit_errors,
    route,
    state_errors,
)

# Queries a block of the reference's attention takes: scores [block, S]
ATTN_BLOCK = 1024
# The gather of a held expert's rows is padded to a multiple of this
# (one compiled program for the counts a layer's experts come to)
ROW_PAD = 256

# Readings: TPU v5e, my chip runs, PR 34 (PERF.md section 6 has the
# seeds). "As stated" is every run of the cell (sixteen seeds for the
# served tokens, seventeen for the path); each fault and each lower
# precision was read on three seeds (2147495111-13 and 2147495131-33)
# by ``tools/kda_mla_moe_controls.py``, which plants it in the program
# or in what the engine hands its adoption program and runs the
# functions the job calls (``serve._check``; ``reference_side``,
# ``served_side``, ``judge``): every one came out ``ok=false`` there.
# The configuration's own noise: with 256 experts, top-8 and random
# weights the last chosen and the first unchosen score lie closer than
# the bf16 rounding of a hidden state moves them, so a tenth of the
# positions goes to another set of experts in some layer and reads 0.14
# or more of the logits' spread away from the reference (up to 0.33),
# the other nine tenths 0.012.
#
# Served tokens (job serve's check: 2 seeded requests of 12288 and
# 16384 tokens x 32 new tokens through the front end; the top reference
# logit at a position less the reference logit of the served token;
# largest and mean). They hold the engine's own compiled programs
# behind the front end against what is WRONG, not against a lower
# precision. As served: largest 0.11-0.67, mean 0.0038-0.0247
# (sixteen seeds). Planted in this cell's own admissions: state and
# tail adopted into the neighbouring row 4.71-7.13 / 1.90-1.93; the
# tail as it stood one token earlier 4.23-6.56 / 0.756-0.799. The
# largest's limit lies x 3.0 above 0.67 and x 2.1 below 4.23, the
# mean's x 6.1 above 0.0247 and x 5.0 below 0.756. What they do NOT
# see is the latent: scattered through the neighbouring row's page
# table (every page of it lost) it reads 0.41-0.65 / 0.0199-0.0223,
# with every eighth page lost the very tokens of the sound run: random
# weights make a softmax over 16 k tokens average its values nearly
# away. The latent's adoption is held by ``ADOPTED_*`` below.
SERVE_LOGIT_GAP = 2.0
SERVE_MEAN_GAP = 0.15
# ||served path - reference|| / ||reference - its mean|| a position
# (``relative_logit_errors``), teacher-forced over one 16384-token
# sequence: 32 rows, each prefilled to a length of its own
# (12384-16352) and adopted into its latent pages, state and tail, then
# 32 one-token steps over all rows. The MEDIAN over the 1024 decode
# positions: as stated 0.01232-0.01261; over the 32 prefills' own rows
# 0.01202-0.01277; a scan that does not freeze its state past
# ``length`` (what a padded bucket does to a recurrence) 0.937-0.965 in
# the decode positions. The limit holds both medians: x 2.4 above
# 0.0127, x 31 below 0.937. No LOWER PRECISION parts from the stated
# one here: each is held where it is alone, below.
PATH_ERR = 0.03
# ... and their 90th percentile, which a fault in a few rows or steps
# moves before the median: as stated 0.139-0.153 (the tenth of the
# positions whose routing flipped); the unfrozen scan 1.198-1.216.
# Between 0.153 (x 2.0) and 1.198 (x 4.0 below).
PATH_ERR_P90 = 0.3
# ||S - S_ref||_F / ||S_ref||_F a head (``state_errors``), the median
# over the four KDA layers' 128 heads, of (a) the state a prefill of
# the WHOLE sequence leaves a row and (b) the state of the row whose 32
# decode steps end at the sequence's end: as stated (a) 0.0137-0.0353,
# (b) 0.0130-0.0341 (the 90th percentile over heads 0.037-0.180); the
# unfrozen scan leaves (a) alone and reads 0.577-0.591 in (b). Between
# 0.0353 (x 3.4) and 0.577 (x 4.8 below). The same limit holds (c),
# the state the ENGINE's own admission leaves (``ADOPTED_*`` below).
PATH_STATE_ERR = 0.12
# The same ratio, its LARGEST over a layer's 32 heads, for the
# program's two state kernels alone (``builder.kda_kernel_state``: the
# chunked scan over 16352 tokens, then 32 one-token updates, the state
# kept in the row's own float32 array) on this reference's own float32
# q, k, v, decay and beta of the last KDA layer, against
# ``kda_recurrence`` on the same numbers: as stated 2.7e-5 - 9.5e-5;
# the state kept in bfloat16 2.06e-3 - 2.48e-3: NOT correct, by this
# limit alone (x 3.2 above the one, x 6.9 below the other).
KERNEL_STATE_ERR = 3e-4
# ||program's expert FFN - reference's|| / ||reference's|| a token, on
# the reference's own FFN inputs (rounded to the served type), the held
# share in both, 4 x 1024 tokens, 90th percentile: as stated
# 0.003574-0.003597; the routed experts' weights through fp8 (e4m3, a
# scale an output channel) 0.02824-0.02835 (without a scale 0.0353):
# NOT correct, by this limit alone (x 1.67 above the one, x 4.7 below
# the other; int8 experts are not measured here: the sibling family's
# read 0.0042 against 0.0034 and were not parted, which is why the
# limit sits low in its room).
FFN_ERR = 0.006
# Share of those tokens (the reference's margin on that input above
# ROUTE_DECIDED) whose chosen set differs from the reference's: as
# stated 0 of about 4040 in every seed; a router whose logits and
# scores are each rounded to bfloat16 0.131-0.133 (and ``ffn_err_p90``
# 0.206-0.216 with it): NOT correct, by this limit and ``FFN_ERR``.
ROUTE_ELSEWHERE = 0.01
# ||program's MLA mixer - reference's|| / ||reference's|| a row, its
# 90th percentile over 32 rows: the program's one-token ABSORBED step
# over latent pages a materialised 16384-token prefill wrote
# (``builder.mla_step_outputs``, a 1152-page table, the span ladder's
# top rung), on the reference's own mixer input rounded to the served
# type, against this reference's K and V per head made from the query
# and the latent AS A DEPLOYMENT IN THESE TYPES HOLDS THEM
# (``nope_mla(rounded=)``: the projections' results in the served type,
# the latent in the cache's; everything after float32), so that the
# reading is the contraction's own error: as stated 0.002885-0.002939
# (six seeds; against the unrounded reference 0.00297-0.00311, ten
# seeds: sharing the stored roundings takes a twentieth off and
# halves the scatter over seeds); the two latent contractions' results
# and the softmax between them (scaled scores, exponentials, their sum,
# the quotient) each rounded to bfloat16 where float32 is stated
# 0.003666-0.003736 (three seeds); the latent through fp8 (e4m3)
# 0.01066-0.01118. The limit lies between the first two, and the room
# is NARROW BY NATURE (x 1.12 above the one, x 1.11 below the other):
# the chip's matrix unit accumulates in float32 whatever type its
# result is rounded to, so the nearest lower precision is two more
# bfloat16 roundings (0.0016 each, in quadrature) beside the three or
# four the stated program makes after the softmax (its probabilities,
# the latent-space result, the value map's and the output projection's
# results: 0.0029 together), and no reading of the mixer's output can
# part 0.0029 from sqrt(0.0029^2 + 0.0022^2) by more. It holds because
# each reading is an average over 32 rows x 2304 outputs of one
# deterministic rounding: over seeds the stated one scatters by 2e-5
# (the limit is 18 of those above its largest), the lowered one by
# 4e-5 (9 below its smallest).
MLA_STEP_ERR = 0.0033
# What the ENGINE's own admission leaves a row, read back as its
# adoption program returns (``builder.adopted_by_engine``: a
# ``PagedServingEngine`` built as the cell builds it admits the whole
# 16384-token sequence through its own prefill program, page claim and
# ``adopt_state_body``; the latent is read THROUGH the row's page
# table, state and tail out of the row), against this reference's
# stored latent (``mla_latent``), final states and tails
# (``kda_tail``). The served tokens cannot see the latent at these
# lengths and the path check above runs the builder's own jits, so
# these hold the one mechanism the configuration forces: a [1, 16384,
# 640] block scattered into pages beside a state and a tail copied
# into a row, in one program, where the engine's own tables say.
# ||got - want|| / ||want|| a token of the latent, a position of the
# tail (4 layers x 3), ``state_errors`` a head of the state (its limit
# is ``PATH_STATE_ERR``). As stated, seven seeds: the latent's median
# 0.0108-0.0111 (the layer's input as bf16 layers before it left it)
# and 90th percentile 0.0872-0.0893 (the positions whose routing
# flipped; largest 0.23-0.27), the tail's median 0.0084-0.0093
# (largest 0.011-0.174), the state's median 0.0135-0.0339. Planted in
# what the engine hands its adoption program, three seeds: the latent
# through the neighbouring row's page table 1.0 / 1.0 (the row's pages
# hold nothing); every eighth page lost 0.0111-0.0112 / 1.0 (the
# median cannot see an eighth: the percentile is for that); state and
# tail into the neighbouring row 1.0 and 1.0; the tail as it stood one
# token earlier 1.388-1.400. ``ADOPTED_ERR`` holds the two medians (x 9
# above 0.0111, x 10 below 1.0), ``ADOPTED_LATENT_P90`` the percentile
# (x 3.4 above 0.0893, x 3.3 below 1.0), ``PATH_STATE_ERR`` the state
# (x 3.5 above 0.0339, x 8.3 below 1.0).
ADOPTED_ERR = 0.1
ADOPTED_LATENT_P90 = 0.3

_HI = "highest"
_F32 = jnp.float32


# ------------------------------------------------------------------- MLA
def _rounder(rounded):
    """``(as_, served, stored)`` of ``rounded = (served type, cache
    type)``: ``as_(a, t)`` rounds ``a`` to ``t`` and hands it back in
    float32; None rounds nothing."""
    if rounded is None:
        return (lambda a, t: a), None, None
    return (lambda a, t: a.astype(t).astype(_F32)), rounded[0], rounded[1]


def _latent(x, w, eps, rounded):
    """``[ckv | k_pe]`` of ``x [S, C]`` as two arrays ``[S, kvl]``,
    ``[S, dr]`` (``k_pe`` not rotated)."""
    as_, served, stored = _rounder(rounded)
    kvl = w["kv_b_proj.weight"].shape[0]
    kva = as_(_mm(x, w["kv_a_proj.weight"]), served)
    ckv = as_(_rms(kva[:, :kvl], w["kv_a_layernorm.weight"], eps), served)
    return as_(ckv, stored), as_(kva[:, kvl:], stored)


def nope_mla(x, w, *, heads, dn, dr, dv, eps, rounded=None):
    """``x [S, C]`` -> ``y [S, C]``; ``w`` the mixer's weights by their
    names relative to it. K and V per head for every position, a head
    at a time, ``ATTN_BLOCK`` queries at a time. ``rounded`` ``(served
    type, cache type)``: the query and ``[ckv | k_pe]`` are rounded
    where a deployment in those types rounds them (the two projections'
    results and the norm's to the served type, the latent then to the
    type it is stored in) and everything after is float32 as ever: what
    ``MLA_STEP_ERR`` holds the absorbed step against, so that the
    reading is the contraction's own error and not the stored
    latent's."""
    s = x.shape[0]
    as_, served, _ = _rounder(rounded)
    q = as_(_mm(x, w["q_proj.weight"]), served).reshape(s, heads, dn + dr)
    ckv, k_pe = _latent(x, w, eps, rounded)
    kv = _mm(ckv, w["kv_b_proj.weight"]).reshape(s, heads, dn + dv)
    scale = (dn + dr) ** -0.5
    blocks = -(-s // ATTN_BLOCK)
    q = jnp.pad(q, ((0, blocks * ATTN_BLOCK - s), (0, 0), (0, 0)))
    cols = jnp.arange(s)

    def one_head(args):
        qh, kn, v = args                       # [S', dn + dr], [S, dn | dv]
        k = jnp.concatenate([kn, k_pe], -1)

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, ATTN_BLOCK)
            sc = jnp.matmul(qb, k.T, precision=_HI) * scale
            rows = start + jnp.arange(ATTN_BLOCK)
            p = jax.nn.softmax(
                jnp.where(rows[:, None] >= cols[None, :], sc, -jnp.inf), -1)
            return jnp.matmul(p, v, precision=_HI)

        return jax.lax.map(one_block, jnp.arange(blocks) * ATTN_BLOCK)

    per_head = lambda a: jnp.swapaxes(a, 0, 1)
    o = jax.lax.map(one_head, (per_head(q), per_head(kv[..., :dn]),
                               per_head(kv[..., dn:])))   # [H, blocks, B, dv]
    o = per_head(o.reshape(heads, -1, dv)[:, :s])
    return _mm(o.reshape(s, heads * dv), w["o_proj.weight"])


# --------------------------------------------------------------- experts
@functools.partial(jax.jit, static_argnames=("moe", "share"))
def _route_share(h, w_gate, *, moe, share):
    """The router over ALL experts, then for the held share: the
    weights ``[S, held]`` (zero where a row did not choose the expert),
    the rows of each held expert first in ``order [S, held]``, and the
    most rows one held expert got."""
    first, held = share
    dense, chosen, margin = route(h, w_gate, **dict(moe))
    mine = dense[:, first:first + held]
    took = jnp.zeros(dense.shape, bool).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(True)[
        :, first:first + held]
    order = jnp.argsort(~took, axis=0, stable=True)
    return mine, order, jnp.max(jnp.sum(took, 0)), chosen, margin


@functools.partial(jax.jit, static_argnames=("cap",))
def _held_experts(h, mine, order, w_gate_up, w_down, *, cap):
    """``sum_e mine[:, e] * SwiGLU_e(h)`` over the stacked held
    experts, each on its first ``cap`` rows of ``order`` (every row
    that chose it is among them; a row that did not has weight zero):
    a plain loop, one expert raised to float32 at a time."""
    def one(acc, xs):
        rows, col, gu, dn = xs
        y = col[rows][:, None] * _swiglu(h[rows], gu, dn)
        return acc.at[rows].add(y), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, _F32),
        (order[:cap].T, mine.T.astype(_F32), w_gate_up, w_down))
    return acc


_shared = jax.jit(_swiglu)


def expert_ffn(h, w, *, moe, share):
    """The expert FFN alone on a GIVEN input ``h [T, C]``; ``w`` the
    ``mlp.`` weights of one layer by their names relative to it, the
    stacked experts being those of ``share = (first, held)``. Returns
    ``(routed part [T, C], shared expert's [T, C], chosen [T, k],
    margin [T])``: the layer's output is the sum of the first two."""
    h = jnp.asarray(h).astype(_F32)
    mine, order, most, chosen, margin = _route_share(
        h, w["gate_weight"], moe=moe, share=share)
    cap = min(h.shape[0], ROW_PAD * -(-max(int(most), 1) // ROW_PAD))
    routed = _held_experts(h, mine, order, w["experts_gate_up"],
                           w["experts_down"], cap=cap)
    shared = _shared(h, w["shared_expert.gate_up_proj.weight"],
                     w["shared_expert.down_proj.weight"])
    return routed, shared, chosen, margin


# ---------------------------------------------------------------- layers
_STATIC = ("mla", "heads", "dn", "dr", "dv", "kda_heads", "kda_dim", "eps")


@functools.partial(jax.jit, static_argnames=_STATIC)
def mix(x, w, *, mla, heads, dn, dr, dv, kda_heads, kda_dim, eps):
    """The mixer half of one layer on ``x [S, C]``; ``w`` the layer's
    weights by their names relative to it. Returns ``(x, final KDA
    state or None, the mixer's input [S, C], the FFN's input [S,
    C])``."""
    h = _rms(x, w["input_layernorm.weight"], eps)
    mixer = {k[len("mixer."):]: v for k, v in w.items()
             if k.startswith("mixer.")}
    state = None
    if mla:
        x = x + nope_mla(h, mixer, heads=heads, dn=dn, dr=dr, dv=dv,
                         eps=eps)
    else:
        y, state = kda_mixer(h, mixer, heads=kda_heads, dim=kda_dim,
                             eps=eps, neg_eigval=False)
        x = x + y
    return x, state, h, _rms(x, w["post_attention_layernorm.weight"], eps)


@jax.jit
def _dense_ffn(x, ffn_in, w_gate_up, w_down):
    return x + _swiglu(ffn_in, w_gate_up, w_down)


def is_mla(cfg, i):
    """Layer ``i`` (from 0): the published lists number from 1."""
    return i + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def moe_static(cfg):
    """The routing constants of ``cfg``, as ``expert_ffn`` takes them
    (``moe=``)."""
    return (("top_k", int(cfg["num_experts_per_token"])),
            ("scale", float(cfg["routed_scaling_factor"])),
            ("renorm", bool(cfg.get("moe_renormalize", True))))


def share_of(cfg):
    """``(first, held)`` of a configuration file: ``num_experts``
    counts the experts held here, beginning at ``experts_first``."""
    return int(cfg.get("experts_first", 0)), int(cfg["num_experts"])


def mixer_static(cfg):
    lin = cfg["linear_attn_config"]
    return dict(
        heads=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        kda_heads=lin["num_heads"], kda_dim=lin["head_dim"],
        eps=float(cfg["rms_norm_eps"]))


def layer_weights(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(weights, cfg, ids, routing=None, states=None):
    """The hidden state after the last layer ``[S, C]`` (before the
    final norm) of one sequence ``ids [S]``. ``routing``, a dict, gets
    every EXPERT layer's ``(chosen [S, k], margin [S], FFN input [S,
    C])`` by layer index; ``states``, a dict, every layer's ``(final
    KDA state [H, d, d] or None in an MLA layer, mixer input [S, C])``
    by layer index."""
    x = jnp.take(weights["model.embed_tokens.weight"], ids, axis=0) \
        .astype(_F32)
    st, moe, share = mixer_static(cfg), moe_static(cfg), share_of(cfg)
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, f"model.layers.{i}.")
        x, state, mixer_in, ffn_in = mix(x, w, mla=is_mla(cfg, i), **st)
        if states is not None:
            states[i] = (state, mixer_in)
        if is_dense(cfg, i):
            x = _dense_ffn(x, ffn_in, w["mlp.gate_up_proj.weight"],
                           w["mlp.down_proj.weight"])
            continue
        routed, shared, chosen, margin = expert_ffn(
            ffn_in, layer_weights(w, "mlp."), moe=moe, share=share)
        x = x + routed + shared
        if routing is not None:
            routing[i] = (chosen, margin, ffn_in)
    return x


def logits(weights, cfg, ids, rows=None, routing=None, states=None):
    """Float32 logits of one sequence ``ids [S]``: ``[S, vocab]``, or
    only at the positions ``rows``."""
    h = hidden(weights, cfg, ids, routing, states)
    if rows is not None:
        h = h[rows]
    return head(h, weights["model.norm.weight"], weights["lm_head.weight"],
                eps=float(cfg["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("heads", "dn", "dr", "dv",
                                             "eps", "rounded"))
def mla_mixer(x, w, *, heads, dn, dr, dv, eps, rounded=None):
    """``nope_mla`` of a GIVEN mixer input: what the program's absorbed
    one-token step is held to on the same numbers, ``rounded`` (type
    names) as ``nope_mla`` takes it."""
    return nope_mla(x.astype(_F32), w, heads=heads, dn=dn, dr=dr, dv=dv,
                    eps=eps, rounded=rounded)


@functools.partial(jax.jit, static_argnames=("eps", "rounded"))
def mla_latent(x, w, *, eps, rounded=None):
    """``[ckv | k_pe]`` ``[S, kvl + dr]`` of a GIVEN mixer input: what
    a row's latent pages are held to after the engine's adoption."""
    return jnp.concatenate(_latent(x.astype(_F32), w, eps, rounded), -1)


@jax.jit
def kda_tail(x, w):
    """The convolution's inputs ``x W_qkv`` at the last ``K - 1``
    positions of a GIVEN mixer input ``[S, C]``: the tail a row keeps
    after a prompt ``[K - 1, channels]``."""
    taps = w["conv_weight"].shape[0]
    return _mm(x[x.shape[0] - (taps - 1):].astype(_F32), w["qkv_proj.weight"])


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
