"""Solar-Open2 decoder: a stack whose layers differ in kind. Three
layers in four mix tokens by KDA (Kimi Delta Attention, arXiv
2510.26692: a gated delta rule with a decay a channel, behind a short
causal convolution) and keep a STATE a row, whatever the row's length;
every fourth is gated softmax attention without rope (NoPE GQA) and
keeps K and V a token. Every layer's FFN is sigmoid-routed experts with
one shared expert, and the layer can be told which experts it holds.

Written from the public ``config.json`` of ``Solar-Open2-250B``
(``model_type`` ``solar_open2``). Pre-norm residual layers, ``x <- x +
Mixer(RMSNorm(x))`` then ``x <- x + FFN(RMSNorm(x))``; final RMSNorm,
untied head.

- **KDA** (``gqa_layers`` names the others), per head ``h``, ``d_k =
  d_v = linear_attn_config.head_dim``::

      [q~ | k~ | v~] = x W_qkv                  # one gemm, no bias
      q', k', v' = SiLU(causal depthwise conv_K(q~, k~, v~))
      q = L2norm(q') * d_k^-0.5;  k = L2norm(k');  v = v'
      g = -exp(A_log_h) * softplus(x W_f1 W_f2 + dt_bias)   # [d_k], <= 0
      beta = 2 sigmoid(x W_b)       # 2: kda_allow_neg_eigval, else 1
      S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T
      o_t = S_t^T q_t
      y = [RMSNorm_w(o) * sigmoid(x W_g1 W_g2 + b_g)] W_o

  ``S`` ``[d_k, d_v]`` a head is float32 and zero where a sequence
  starts. Three forms compute it: ``kda_step`` (one token a row: the
  decode step), ``kda_chunked`` (chunks of ``kda_chunk`` tokens:
  prefill and the cacheless forward) and the recurrence as written
  (the reference's); they agree to rounding.
- **The chunked form**, with ``G`` the decay summed from a chunk's
  start: only the state a chunk starts from depends on the chunk
  before, so everything else is made for ALL chunks at once, outside
  the loop: ``A_ij = sum_c k_i k_j e^{G_i - G_j}`` and ``P`` (the same
  with ``q_i``) for ``i >= j``, the inverse ``T`` of the unit lower
  triangular ``I + Diag(beta) A_strict`` by forward substitution, ``W =
  T (beta k e^G)`` and ``U0 = T (beta v)``. The ``lax.scan`` over
  chunks carries the state alone: ``u = U0 - W S_0``, ``o = (q e^G) S_0
  + P u``, ``S_C = Diag(e^{G_C}) S_0 + (k e^{G_C - G})^T u``. No ``[C,
  C, d]`` tensor of pairwise decays is formed: a chunk is cut into
  sub-blocks of 16 tokens, and rows ``i`` of sub-block ``I`` meet the
  columns ``j`` of the sub-blocks before it in one float32 matmul,
  ``A_ij = (k_i e^{G_i - r_I}) . (k_j e^{r_I - G_j})`` with ``r_I``
  the row of ``G`` at ``I``'s first token. ``g <= 0``, so ``G`` only
  falls along a chunk and ``G_i <= r_I <= G_j``: both exponents are
  ``<= 0``, no factor exceeds 1 and none is the reciprocal of a decay,
  however strong (the product of the two is ``e^{G_i - G_j}`` itself).
  Only the four diagonal 16 x 16 blocks form ``e^{G_i - G_j}`` pair by
  pair, for their ``i >= j``.
- **What a row keeps** (``SolarOpen2Config.row_layout``): a KDA layer
  the state ``[H, d_k, d_v]`` float32 and the convolution's TAIL, the
  last ``K - 1`` rows of ``[q~ | k~ | v~]``; a GQA layer nothing. What
  a token costs (``cache_layout``): a GQA layer a K and a V of ``[kvH,
  D]``; a KDA layer nothing. A layer's cache tuple is its token arrays
  then its row arrays (``generation.alloc_kv_caches``).
- **Padding.** A right-padded bucket is exact for K/V because decode
  overwrites pad slots before reading them. A recurrence has no such
  slots: the scan takes ``length`` and, for ``t >= length``, applies
  ``beta = 0, g = 0`` (the state's transition is then the identity,
  bitwise) and takes the tail at ``length``.
- **GQA**: ``q, k, v = x W_q, x W_k, x W_v``, no rope, no q/k norm,
  causal ``softmax(q k^T / sqrt(D)) v`` grouped by KV head, ``y = [o *
  sigmoid(x W_gate)] W_o``. Cache addressing is ``quantization.kv``'s,
  as Llama's.
- **Experts**: ``xing4``'s router and dropless dispatch; with
  ``experts_held`` the layer holds experts ``[experts_first,
  experts_first + experts_held)`` of ``n_routed_experts``: the router
  stays that wide, top-k is taken over all of them, the weights are
  renormalised over all k, and the layer adds the terms of the experts
  it holds (what the absent ones would add is left out: their chips
  add it).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..core import dispatch
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..quantization import kv as qkv
from .llama import _cache_attention
from .xing4 import (
    Xing4MLP,
    _causal_attention,
    dispatch_rows,
    experts_touched,
    moe_choose,
    moe_dispatch,
    moe_scores,
    moe_weights,
)

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _linear_attn_default():
    return {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None}


class KDADims:
    """What a config with ``linear_attn_config`` derives from it: the
    KDA mixer's sizes and what a KDA layer keeps a ROW. Every family
    whose layers are :class:`SolarOpen2KDA` states them through this."""

    @property
    def kda_heads(self):
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_head_dim(self):
        return int(self.linear_attn_config["head_dim"])

    @property
    def kda_conv(self):
        return int(self.linear_attn_config["short_conv_kernel_size"])

    def kda_row_arrays(self):
        """``(shape, dtype)`` of the arrays a KDA layer keeps a row
        (dtype None: the cache's): its state, float32 whatever the
        cache is stored in, and its convolution tail."""
        h, d = self.kda_heads, self.kda_head_dim
        return (((h, d, d), "float32"),
                ((self.kda_conv - 1, 3 * h * d), None))


@dataclass
class SolarOpen2Config(KDADims):
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240       # unused: no layer is dense
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: dict = field(default_factory=_linear_attn_default)
    gqa_interval: int = 3
    gqa_layers: tuple | None = None      # None: every (interval + 1)th
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the share of the experts this program holds (None: all of them)
    experts_first: int = 0
    experts_held: int | None = None
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    # tokens a step of the chunked scan takes
    kda_chunk: int = 64

    def __post_init__(self):
        if self.use_rope or self.kda_use_full_proj \
                or self.first_k_dense_replace:
            raise ValueError(
                "SolarOpen2: written for use_rope false, low-rank KDA "
                "gates and no leading dense layer")
        last = self.experts_first + self.held
        if not 0 <= self.experts_first <= last <= self.n_routed_experts:
            raise ValueError(
                f"experts [{self.experts_first}, {last}) are not among "
                f"{self.n_routed_experts}")

    @property
    def kv_heads(self):
        return self.num_key_value_heads

    @property
    def held(self):
        """Experts this program holds of a layer's ``n_routed_experts``."""
        return self.n_routed_experts if self.experts_held is None \
            else int(self.experts_held)

    def is_gqa(self, i):
        if self.gqa_layers is not None:
            return i in tuple(self.gqa_layers)
        return i % (self.gqa_interval + 1) == 0

    def cache_layout(self):
        """What a TOKEN costs a layer: a K and a V of ``[kvH, D]`` in a
        GQA layer, nothing in a KDA layer."""
        pair = ((self.kv_heads, self.head_dim),) * 2
        return [pair if self.is_gqa(i) else ()
                for i in range(self.num_hidden_layers)]

    def row_layout(self):
        """What a ROW keeps a layer: a KDA layer its state and its
        convolution tail (``kda_row_arrays``); a GQA layer nothing."""
        kept = self.kda_row_arrays()
        return [() if self.is_gqa(i) else kept
                for i in range(self.num_hidden_layers)]

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                "num_heads": 4, "num_kv_heads": None},
            n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=128, kda_chunk=8,
        )
        base.update(kw)
        return SolarOpen2Config(**base)


# ------------------------------------------------------------------- KDA
def kda_conv(x, w, tail=None, length=None):
    """Depthwise causal convolution then SiLU: ``x`` ``[B, S, C]`` (the
    rows before the convolution), ``w`` ``[K, C]`` one filter a
    channel, ``tail`` ``[B, K - 1, C]`` the rows before ``x`` (None:
    zeros, a sequence's start). Returns ``(y [B, S, C], new_tail)``,
    the tail taken behind row ``length - 1`` (None: the last)."""
    k = w.shape[0]
    b, s, c = x.shape
    if tail is None:
        tail = jnp.zeros((b, k - 1, c), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(_F32)
    y = sum(wf[j] * xp[:, j:j + s].astype(_F32) for j in range(k))
    start = s if length is None else jnp.asarray(length, jnp.int32)
    new_tail = jax.lax.dynamic_slice_in_dim(xp, start, k - 1, axis=1)
    return jax.nn.silu(y).astype(x.dtype), new_tail


def kda_qkv(y, heads, dim):
    """The convolution's output ``[B, S, 3 H d]`` -> ``q`` (L2-normed,
    times ``d^-0.5``), ``k`` (L2-normed), ``v``: ``[B, S, H, d]``
    float32."""
    b, s, _ = y.shape
    q, k, v = (a.reshape(b, s, heads, dim).astype(_F32)
               for a in jnp.split(y, 3, axis=-1))
    l2 = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    return l2(q) * dim ** -0.5, l2(k), v


def kda_decay(f, a_log, dt_bias, heads, dim):
    """Log decay ``g`` ``[B, S, H, d]`` (float32, <= 0) from the gate
    projection's output ``f`` ``[B, S, H d]``."""
    b, s, _ = f.shape
    sp = jax.nn.softplus(f.astype(_F32) + dt_bias.astype(_F32))
    return -jnp.exp(a_log.astype(_F32))[:, None] \
        * sp.reshape(b, s, heads, dim)


def kda_step(q, k, v, g, beta, state):
    """One token a row: ``q``, ``k``, ``g`` ``[B, H, dk]``, ``v`` ``[B,
    H, dv]``, ``beta`` ``[B, H]``, ``state`` ``[B, H, dk, dv]``
    float32. Returns ``(o [B, H, dv], new state)``. The state is read
    twice and written once: a pass that takes ``k^T Diag(a) S`` and
    ``q^T Diag(a) S`` together, and the rank-one update; the read-out
    ``S_t^T q`` is then ``q^T Diag(a) S + (q . k) u``."""
    a = jnp.exp(g)
    decayed = a[..., None] * state
    r = jnp.sum(k[..., None] * decayed, axis=-2)
    o0 = jnp.sum(q[..., None] * decayed, axis=-2)
    u = beta[..., None] * (v - r)
    new = decayed + k[..., None] * u[..., None, :]
    o = o0 + jnp.sum(q * k, -1, keepdims=True) * u
    return o, new


# chunk-heads (chunks x rows x heads) whose chunk-local arrays are made
# at once: 4 chunks of 64 heads, 8 of 32. Small on purpose: a group's
# arrays are then 8 MB each and the chip keeps them close; with 2048 (a
# whole 2048-token prefill of 64 heads) the same operations took half
# as long again, and 128 gains nothing more (PERF.md section 6, PR 35)
_KDA_GROUP = 256
_mm = lambda x, y: jnp.matmul(x, y, precision=_HI)
_t = lambda x: jnp.swapaxes(x, -1, -2)


def _blocks_minor(a, sub):
    """``[*lead, nb sub, c]`` -> ``[sub, c, nb, L]``: the rows cut into
    blocks of ``sub`` and every block of every leading index laid
    minor (``L`` = ``lead`` flattened). What is done a block at a time
    is then dense over the blocks."""
    return a.reshape((-1, a.shape[-2] // sub, sub, a.shape[-1])) \
        .transpose(2, 3, 1, 0)


def _block_diagonal(blocks, lead):
    """``[sub, sub, nb, L]`` (``_blocks_minor``'s layout) -> ``[*lead,
    nb sub, nb sub]`` with the blocks on the diagonal."""
    sub, _, nb, _ = blocks.shape
    wide = jnp.concatenate([
        jnp.pad(blocks[:, :, i], ((0, 0), (i * sub, (nb - 1 - i) * sub),
                                  (0, 0))) for i in range(nb)], 0)
    return jnp.moveaxis(wide, -1, 0).reshape(lead + (nb * sub, nb * sub))


def _kda_pairwise(qc, kc, big, sub):
    """``A_ij = sum_c k_i[c] k_j[c] e^{G_i[c] - G_j[c]}`` and ``P`` (the
    same with ``q_i``) for ``i >= j``: ``qc``, ``kc``, ``big`` (``G``)
    ``[..., C, d]``. By sub-blocks of ``sub`` rows. A diagonal block
    forms ``e^{G_i - G_j}`` for its ``i >= j`` (the blocks laid minor:
    the sum over channels is then over whole vectors of blocks); rows
    ``i`` of sub-block ``I`` against the columns ``j`` before it are ONE
    product ``(x_i e^{G_i - r_I}) . (k_j e^{r_I - G_j})``, ``r_I`` the
    first row of ``G`` in ``I``: ``G`` only falls, so ``G_i <= r_I <=
    G_j`` and neither exponent is positive. Returns ``A``'s and ``P``'s
    diagonal blocks ``[sub, sub, nb, L]`` (``_blocks_minor``'s layout;
    zero above the diagonal) and their other blocks in place ``[..., C,
    C]`` (zero elsewhere)."""
    c, d = kc.shape[-2:]
    nb = c // sub
    lead = kc.shape[:-2]
    qm, km, gm = (_blocks_minor(a, sub) for a in (qc, kc, big))
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None, None, None]
    ke = km[None] * jnp.exp(jnp.where(low, gm[:, None] - gm[None], -jnp.inf))
    # one reduction with two results, so that one pass forms ``e``
    p_diag, a_diag = jax.lax.reduce(
        (qm[:, None] * ke, km[:, None] * ke), (jnp.zeros((), _F32),) * 2,
        lambda x, y: (x[0] + y[0], x[1] + y[1]), (2,))
    if nb == 1:
        none = jnp.zeros(lead + (c, c), _F32)
        return a_diag, none, p_diag, none
    # sub-blocks 1 .. nb - 1 (nothing lies before the first), q's rows
    # above k's so that one product serves P and A
    blk = lambda a: a.reshape(lead + (nb, sub, d))[..., 1:, :, :]
    gb = blk(big)
    first = gb[..., :1, :]
    before = jnp.arange(c) < (jnp.arange(1, nb) * sub)[:, None]
    right = _t(kc[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], first - big[..., None, :, :], -jnp.inf)))
    left = jnp.concatenate([blk(qc), blk(kc)], -2) \
        * jnp.tile(jnp.exp(gb - first), (2, 1))
    off = _mm(left, right)
    top = [(0, 0)] * len(lead) + [(1, 0), (0, 0), (0, 0)]
    p_off, a_off = (jnp.pad(x, top).reshape(lead + (c, c))
                    for x in (off[..., :sub, :], off[..., sub:, :]))
    return a_diag, a_off, p_diag, p_off


def _unit_lower_inverse(diag, off):
    """``(I + L)^-1`` for strictly lower triangular ``L`` ``[..., C,
    C]``, given as its diagonal blocks ``diag`` ``[sub, sub, nb, L]``
    (``_blocks_minor``'s layout) and the rest ``off`` ``[..., C, C]``.
    By forward substitution: row by row inside the diagonal blocks
    (``T_i = e_i - sum_{j<i} L_ij T_j``, a row one dense pass over the
    blocks), then block by block, doubling: ``[[A, 0], [M, D]]^-1 =
    [[A^-1, 0], [-D^-1 M A^-1, D^-1]]``. A zero row of ``L`` gives that
    row of the identity, exactly, at every step."""
    sub, c = diag.shape[0], off.shape[-1]
    eye = jnp.eye(sub, dtype=_F32)
    rows = []
    for i in range(sub):
        r = jnp.broadcast_to(eye[i][:, None, None], diag.shape[1:])
        for j in range(i):
            r = r - diag[i, j] * rows[j]
        rows.append(r)
    inv = _block_diagonal(jnp.stack(rows), off.shape[:-2])
    at = jnp.arange(c)
    size = sub
    while size < c:
        corner = (at[:, None] // (2 * size) == at // (2 * size)) \
            & (at[:, None] // size != at // size)
        inv = inv - _mm(_mm(inv, jnp.where(corner, off, 0.0)), inv)
        size *= 2
    return inv


def _kda_chunk_local(qc, kc, vc, gc, bc, sub):
    """What a chunk computes without the state before it, for every
    chunk given (``[..., C, d]``; ``bc`` ``[..., C, 1]``): ``q e^G``,
    ``W``, ``U0``, ``P``, ``k e^{G_C - G}`` and ``e^{G_C}`` as a
    column."""
    lead = kc.shape[:-2]
    c = kc.shape[-2]
    # the running sum as a product: a reduce-window is slow on the chip
    big = _mm(jnp.tril(jnp.ones((c, c), _F32)), gc)
    eg = jnp.exp(big)
    last = big[..., -1:, :]
    a_diag, a_off, p_diag, p_off = _kda_pairwise(qc, kc, big, sub)
    strict = jnp.tril(jnp.ones((sub, sub), bool), -1)[..., None, None]
    inv = _unit_lower_inverse(
        jnp.where(strict, _blocks_minor(bc, sub) * a_diag, 0.0), bc * a_off)
    return (qc * eg, _mm(inv, bc * kc * eg), _mm(inv, bc * vc),
            p_off + _block_diagonal(p_diag, lead),
            kc * jnp.exp(last - big), _t(jnp.exp(last)))


def kda_chunked(q, k, v, g, beta, state, chunk):
    """The same recurrence over ``S`` tokens, ``chunk`` at a time:
    ``q``, ``k``, ``g`` ``[B, S, H, dk]``, ``v`` ``[B, S, H, dv]``,
    ``beta`` ``[B, S, H]``, all float32, ``S`` a multiple of ``chunk``;
    ``state`` ``[B, H, dk, dv]``. With ``G`` the decay summed from the
    chunk's start and ``S_0`` the state there::

        u_i = beta_i (v_i - (k_i e^{G_i})^T S_0 - sum_{j<i} A_ij u_j)
        A_ij = sum_c k_i[c] k_j[c] e^{G_i[c] - G_j[c]}
        o_i = (q_i e^{G_i})^T S_0 + sum_{j<=i} P_ij u_j     (P as A, with q_i)
        S_C = Diag(e^{G_C}) S_0 + sum_j (k_j e^{G_C - G_j}) u_j^T

    Only ``S_0`` depends on the chunk before. Everything else is made
    for all chunks at once (``_kda_chunk_local``; groups of
    ``_KDA_GROUP`` chunk-heads where the sequence is longer), as float32
    matmuls: ``A`` and ``P`` by sub-blocks of ``min(16, chunk)`` tokens
    (``_kda_pairwise``), ``T = (I + Diag(beta) A_strict)^-1``
    (``_unit_lower_inverse``), ``W = T (beta k e^G)``, ``U0 = T (beta
    v)``. The scan over chunks carries the state alone::

        u = U0 - W S_0;   o = (q e^G) S_0 + P u
        S_C = Diag(e^{G_C}) S_0 + (k e^{G_C - G})^T u

    A frozen position (``beta = 0``, ``g = 0``) has a row of the
    identity in ``T``, so its ``u`` is exactly 0 and a chunk of them
    returns ``S_0`` bitwise. Returns ``(o [B, S, H, dv], new state)``."""
    b, s, h, dk = q.shape
    n = s // chunk
    sub = min(16, chunk)
    if chunk % sub:
        raise ValueError(f"kda_chunked: chunk {chunk} is no multiple of "
                         f"its sub-block {sub}")
    m = max(1, min(n, _KDA_GROUP // (b * h)))
    span, groups = m * chunk, -(-n // m)
    # chunks added to fill the last group are frozen ones
    q, k, v, g, beta = (
        jnp.pad(a, [(0, 0), (0, groups * span - s)]
                + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, g, beta))

    def cut(a, i):
        """Group ``i`` as ``[m, B, H, chunk, .]``: chunks lead (the
        scan), heads batch."""
        a = jax.lax.dynamic_slice_in_dim(a, i * span, span, axis=1)
        return jnp.moveaxis(a.reshape((b, m, chunk) + a.shape[2:]),
                            (1, 3), (0, 2))

    def one(s0, xs):
        qe, w, u0, p, kd, decay = xs
        u = u0 - _mm(w, s0)
        s1 = decay * s0 + jnp.einsum("...cd,...cv->...dv", kd, u,
                                     precision=_HI)
        return s1, _mm(qe, s0) + _mm(p, u)

    def group(s0, i):
        s1, o = jax.lax.scan(one, s0, _kda_chunk_local(
            cut(q, i), cut(k, i), cut(v, i), cut(g, i),
            cut(beta, i)[..., None], sub))
        return s1, jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, span, h, -1)

    state, o = jax.lax.scan(group, state.astype(_F32), jnp.arange(groups))
    return jnp.moveaxis(o, 0, 1).reshape(b, groups * span, h, -1)[:, :s], \
        state


def kda_scan(q, k, v, g, beta, state, chunk, length=None):
    """``kda_chunked`` for any ``S``: the sequence is padded on the
    right to whole chunks, and every position from ``length`` (None:
    ``S``) on is frozen, ``beta = 0`` and ``g = 0``: the state's
    transition there is the identity, bitwise, so the state returned is
    the state after ``length`` tokens."""
    s = q.shape[1]
    if length is not None:
        live = jnp.arange(s) < jnp.asarray(length, jnp.int32)
        beta = jnp.where(live[None, :, None], beta, 0.0)
        g = jnp.where(live[None, :, None, None], g, 0.0)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    o, state = kda_chunked(q, k, v, g, beta, state, chunk)
    return o[:, :s], state


class _UniformThrough(I.Initializer):
    """``fn`` of a uniform draw in ``[low, high)``."""

    def __init__(self, low, high, fn):
        self.draw, self.fn = I.Uniform(low, high), fn

    def __call__(self, shape, dtype):
        return self.fn(self.draw(shape, _F32)).astype(dtype)


class SolarOpen2KDA(nn.Layer):
    """The KDA mixer over any config that states ``hidden_size``,
    ``kda_heads``, ``kda_head_dim``, ``kda_conv``, ``kda_chunk``,
    ``kda_allow_neg_eigval`` and ``rms_norm_eps``."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        c, h, d = cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim
        lin = lambda i, o, bias=False: nn.Linear(
            i, o, bias_attr=None if bias else False)
        self.qkv_proj = lin(c, 3 * h * d)
        self.conv_weight = self.create_parameter(
            [cfg.kda_conv, 3 * h * d],
            default_initializer=I.Normal(0.0, cfg.kda_conv ** -0.5))
        self.f_a_proj = lin(c, d)
        self.f_b_proj = lin(d, h * d)
        # the family's initialisation: decay rates log U(1, 16), time
        # steps softplus^-1 of U(0.001, 0.1)
        self.A_log = self.create_parameter(
            [h], default_initializer=_UniformThrough(1.0, 16.0, jnp.log))
        self.dt_bias = self.create_parameter(
            [h * d], default_initializer=_UniformThrough(
                0.001, 0.1, lambda y: y + jnp.log(-jnp.expm1(-y))))
        self.b_proj = lin(c, h)
        self.g_a_proj = lin(c, d)
        self.g_b_proj = lin(d, h * d, bias=True)
        self.o_norm = nn.RMSNorm(d, cfg.rms_norm_eps)
        self.o_proj = lin(h * d, c)

    def forward(self, x, cache=None, pos=None, length=None):
        """``x`` ``[B, S, C]``; ``cache`` the layer's ``(state, tail)``
        or None (a sequence from its start, nothing kept). A scalar
        ``pos`` of 0 starts the sequence: state and tail are taken as
        zero whatever the arrays hold (a recycled block). Returns
        ``(out, new_cache)``."""
        cfg = self.cfg
        h, d = cfg.kda_heads, cfg.kda_head_dim
        b, s = int(x.shape[0]), int(x.shape[1])
        state = tail = None
        if cache is not None:
            state, tail = cache
            p = jnp.asarray(pos)
            if p.ndim == 0:
                fresh = p == 0
                state = jnp.where(fresh, 0.0, state)
                tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
            elif s != 1:
                raise ValueError("KDA: rows at their own positions feed "
                                 f"one token each, got S={s}")
        else:
            state = jnp.zeros((b, h, d, d), _F32)
        with jax.named_scope("kda_proj"):
            pre = self.qkv_proj(x)
            f = self.f_b_proj(self.f_a_proj(x))
            bt = self.b_proj(x)
            gate = self.g_b_proj(self.g_a_proj(x))
        with jax.named_scope("kda_conv"):
            y, new_tail = dispatch.apply(
                "kda_conv", lambda xv, wv: kda_conv(xv, wv, tail, length),
                (pre, self.conv_weight), cache=False)
        neg = 2.0 if cfg.kda_allow_neg_eigval else 1.0

        def mix(yv, fv, bv, a_log, dt_bias):
            with jax.named_scope("kda_gate"):
                q, k, v = kda_qkv(yv, h, d)
                g = kda_decay(fv, a_log, dt_bias, h, d)
                beta = neg * jax.nn.sigmoid(bv.astype(_F32))
            if s == 1:
                with jax.named_scope("kda_step"):
                    o, new = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], state)
                    return o[:, None], new
            with jax.named_scope("kda_chunk"):
                return kda_scan(q, k, v, g, beta, state, cfg.kda_chunk,
                                length)

        o, new_state = dispatch.apply(
            "kda_mix", mix, (y, f, bt, self.A_log, self.dt_bias),
            cache=False)
        with jax.named_scope("kda_gate"):
            o = self.o_norm(o) * dispatch.apply(
                "kda_out_gate", lambda gv: jax.nn.sigmoid(
                    gv.astype(_F32)).reshape(b, s, h, d), (gate,),
                cache=False)
        out = self.o_proj(o.astype(x.dtype).reshape([b, s, h * d]))
        if cache is None:
            return out, None
        # each in the type its array is kept in (row_layout)
        return out, (new_state.value.astype(cache[0].dtype),
                     new_tail.value.astype(cache[1].dtype))


# ------------------------------------------------------------------- GQA
class SolarOpen2Attention(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        c, h, kvh, d = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.kv_heads, cfg.head_dim)
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)
        self.q_proj = lin(c, h * d)
        self.k_proj = lin(c, kvh * d)
        self.v_proj = lin(c, kvh * d)
        self.gate_proj = lin(c, h * d) if cfg.use_gqa_gate else None
        self.o_proj = lin(h * d, c)

    def forward(self, x, cache=None, pos=None, page_table=None):
        """``x`` ``[B, S, C]``; ``cache`` the layer's ``(K, V)``: a
        block or slab ``[B, S_max, kvH, D]`` (``pos`` scalar or ``[B]``)
        or, with ``page_table``, page arenas. Returns ``(out,
        new_cache)``."""
        cfg = self.cfg
        b, s = int(x.shape[0]), int(x.shape[1])
        h, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        q = self.q_proj(x).reshape([b, s, h, d])
        k = self.k_proj(x).reshape([b, s, kvh, d])
        v = self.v_proj(x).reshape([b, s, kvh, d])
        with jax.named_scope("attn_core"):
            out, new_cache = self._core(q, k, v, cache, pos, page_table)
        out = out.reshape([b, s, h * d])
        if self.gate_proj is not None:
            with jax.named_scope("attn_gate"):
                out = out * dispatch.apply(
                    "gqa_gate", lambda gv: jax.nn.sigmoid(
                        gv.astype(_F32)).astype(gv.dtype),
                    (self.gate_proj(x),), cache=False)
        return self.o_proj(out), new_cache

    def _core(self, q, k, v, cache, pos, page_table):
        rep = self.cfg.num_attention_heads // self.cfg.kv_heads
        scale = self.cfg.head_dim ** -0.5

        def causal(qv, kv_, vv):
            # plain causal attention among fresh tokens (flash from the
            # length at which the scores should not lie in HBM)
            return _causal_attention(qv, jnp.repeat(kv_, rep, axis=2),
                                     jnp.repeat(vv, rep, axis=2), scale)

        if cache is None:
            return dispatch.apply("gqa_attention", causal, (q, k, v),
                                  cache=False), None
        p = jnp.asarray(pos)
        fresh = (k.value, v.value)
        attend = lambda views, mask: _cache_attention(
            q, Tensor(views[0]), Tensor(views[1]), mask, None)
        if page_table is not None:
            # the read stops at the batch's longest row (the span
            # ladder of kv.write_and_attend_paged)
            cache, out = qkv.write_and_attend_paged(
                cache, fresh, p, page_table,
                lambda views, mask: attend(views, mask).value,
                q.value.dtype)
            return Tensor(out), cache
        cache, views, cols = qkv.write_and_view(cache, fresh, p,
                                                q.value.dtype)
        if p.ndim == 0 and int(q.shape[1]) == views[0].shape[1]:
            # a chunk as long as its block can only start at 0 (the
            # engines' prefill programs): no mask over the block
            return Tensor(causal(q.value, *fresh)), cache
        return attend(views, qkv.position_mask(
            cols, views[0].shape[1])), cache


# --------------------------------------------------------------- experts
class SolarOpen2MoE(nn.Layer):
    """Sigmoid-routed experts, ``held`` of ``n_routed_experts`` of them
    here, and the shared expert. The router has every expert's column;
    the stacked expert weights only the held ones'. Over any config
    that states the routing keys it reads (``n_routed_experts``,
    ``held``, ``experts_first``, ``num_experts_per_tok``,
    ``norm_topk_prob``, ``routed_scaling_factor``, ``n_shared_experts``,
    ``moe_intermediate_size``)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        c, i = cfg.hidden_size, cfg.moe_intermediate_size
        init = I.Normal(0.0, 0.02)
        self.gate_weight = self.create_parameter(
            [c, cfg.n_routed_experts], default_initializer=init)
        self.experts_gate_up = self.create_parameter(
            [cfg.held, c, 2 * i], default_initializer=init)
        self.experts_down = self.create_parameter(
            [cfg.held, i, c], default_initializer=init)
        self.shared_expert = Xing4MLP(c, i * cfg.n_shared_experts)
        self.last_counts = None

    def route(self, h):
        """The chosen experts ``[T, k]`` (an array, numbered over all
        ``n_routed_experts``) and their weights ``[T, k]``, made to sum
        to one over all ``k`` whoever holds them."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            scores = dispatch.apply("moe_scores", moe_scores,
                                    (h, self.gate_weight), cache=False)
            idx = moe_choose(scores.value, jnp.zeros((), _F32),
                             cfg.num_experts_per_tok)
            w = dispatch.apply(
                "moe_weights", lambda sv: moe_weights(
                    sv, idx, scale=float(cfg.routed_scaling_factor),
                    renorm=bool(cfg.norm_topk_prob)),
                (scores,), cache=False)
        return idx, w

    def forward(self, x):
        cfg = self.cfg
        shape = [int(d) for d in x.shape]
        h = x.reshape([-1, shape[-1]])
        idx, w = self.route(h)
        first, held = cfg.experts_first, cfg.held
        with jax.named_scope("moe_experts"):
            y = dispatch.apply(
                "moe_dispatch",
                lambda hv, wv, gu, dn: moe_dispatch(
                    hv, idx, wv, gu, dn, first=first, held=held),
                (h, w, self.experts_gate_up, self.experts_down),
                cache=False)
            local = idx - first
            here = (local >= 0) & (local < held)
            n_local = jnp.sum(here).astype(jnp.int32)
            self.last_counts = {
                "experts_touched": experts_touched(
                    jnp.where(here, local, held), held),
                "local_assignments": n_local,
                "dispatch_rows": dispatch_rows(idx.size, n_local)}
        with jax.named_scope("shared_expert"):
            y = y + self.shared_expert(h)
        return y.reshape(shape)


# ----------------------------------------------------------------- layers
class SolarOpen2DecoderLayer(nn.Layer):
    def __init__(self, cfg: SolarOpen2Config, gqa: bool):
        super().__init__()
        self.gqa = gqa
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = SolarOpen2Attention(cfg) if gqa else SolarOpen2KDA(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = SolarOpen2MoE(cfg)

    def forward(self, x, cache=None, pos=None, page_table=None,
                length=None):
        h = self.input_layernorm(x)
        if self.gqa:
            a, new_cache = self.mixer(h, cache=cache, pos=pos,
                                      page_table=page_table)
        else:
            a, new_cache = self.mixer(h, cache=cache, pos=pos,
                                      length=length)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


def _array(x):
    return jnp.asarray(x.value if hasattr(x, "value") else x)


class SolarOpen2Model(nn.Layer):
    """A stack whose layers differ in kind: ``make_layer`` says which
    layer stands at ``i`` (a family with other kinds overrides it), and
    every layer takes ``(x, cache, pos, page_table, length)`` and gives
    ``(x, new_cache)``, its cache a tuple of its token arrays then its
    row arrays."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            self.make_layer(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def make_layer(self, cfg, i):
        return SolarOpen2DecoderLayer(cfg, cfg.is_gqa(i))

    def forward(self, input_ids, caches=None, pos=None, page_table=None,
                length=None, apply_final_norm=True):
        """``caches``: a layer's token arrays then its row arrays
        (``alloc_kv_caches``); with ``page_table`` the token arrays are
        page arenas and the row arrays stay a row each. ``length``
        (scalar, traceable): the tokens of a right-padded ``input_ids``
        that count. Returns the hidden state, and the new caches with it
        when given any."""
        if pos is not None:
            pos = _array(pos)
        if page_table is not None:
            page_table = _array(page_table)
        h = self.embed_tokens(input_ids)
        new_caches = []
        for i, layer in enumerate(self.layers):
            h, c2 = layer(h, cache=None if caches is None else caches[i],
                          pos=pos, page_table=page_table, length=length)
            new_caches.append(c2)
        if apply_final_norm:
            h = self.norm(h)
        return h if caches is None else (h, new_caches)


class SolarOpen2ForCausalLM(nn.Layer):
    model_class = SolarOpen2Model

    def __init__(self, config):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError(f"{type(self).__name__}: the head is not tied")
        self.config = config
        self.model = self.model_class(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    # ``generation.prefill`` reads this: one row of logits of a bucket
    head_takes_row = True

    def forward(self, input_ids, attn_mask=None, caches=None, pos=None,
                page_table=None, head_row=None, length=None):
        """The seam every decoder of this package serves through:
        logits ``[B, S, V]``, and with ``caches`` the new caches too.
        ``head_row`` runs the final norm and the head on that one
        position alone; ``length`` freezes the row state past it."""
        if attn_mask is not None:
            raise ValueError(
                f"{type(self).__name__}: no explicit attention mask "
                f"(positions mask the cache, length the state)")
        out = self.model(input_ids, caches=caches, pos=pos,
                         page_table=page_table, length=length,
                         apply_final_norm=False)
        h, new_caches = (out, None) if caches is None else out
        if head_row is not None:
            h = dispatch.apply(
                "head_row", lambda v: jax.lax.dynamic_slice_in_dim(
                    v, jnp.asarray(head_row, jnp.int32), 1, axis=1),
                (h,), cache=False)
        logits = self.lm_head(self.model.norm(h))
        return logits if caches is None else (logits, new_caches)

    def pop_step_counters(self):
        """What the step just traced counted, summed over the expert
        layers (a dense FFN counts nothing): ``experts_touched``, the
        HELD experts that got at least one token, ``local_assignments``,
        the assignments that landed on held experts, and
        ``dispatch_rows``, the sorted rows the grouped matmuls were
        handed (the rung of ``xing4.row_ladder`` that holds a layer's
        local assignments)."""
        total = {}
        for layer in self.model.layers:
            counts = getattr(layer.mlp, "last_counts", None)
            if counts is None:
                continue
            layer.mlp.last_counts = None
            for name, value in counts.items():
                total[name] = total.get(name, 0) + value
        return total

    def num_params(self):
        return sum(int(p.size) for p in self.parameters())

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=0, cache_dtype=None):
        from .generation import DEFAULT_CACHE_DTYPE
        from .generation import generate as _generate

        return _generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            top_p=top_p, eos_token_id=eos_token_id, seed=seed,
            cache_dtype=cache_dtype or DEFAULT_CACHE_DTYPE,
        )
