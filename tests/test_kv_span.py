"""The span ladder of the paged decode read
(``quantization.kv.write_and_attend_paged``): a decode step through a
page table gathers and contracts only as many table columns as the
batch's longest row needs, picked on the device from ``pos`` inside the
one program. Every rung is the whole-table read at a narrower width, so
logits and written pages must be BITWISE what the step gives with the
ladder forced to its last rung (the read as it was), wherever the
longest row sits."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, Xing4Config,
                               Xing4ForCausalLM)
from paddle_tpu.models import generation as gen
from paddle_tpu.quantization import kv as qkv

# the cells' page size; 256 columns, so the toy nets get 256 positions
ROWS, PAGES, PS = 4, 16, 16
LADDER = qkv.span_ladder(PAGES)



def _llama():
    return LlamaForCausalLM(LlamaConfig.tiny(
        num_key_value_heads=2, max_position_embeddings=PAGES * PS))


def _xing4():
    return Xing4ForCausalLM(Xing4Config.tiny(
        hc_sinkhorn_iters=2, max_position_embeddings=PAGES * PS))


NETS = {
    "llama_gqa-bf16": (_llama, "bfloat16"),
    "llama_gqa-int8": (_llama, "int8"),
    "xing4-bf16": (_xing4, "bfloat16"),
}


def _noise(leaf, key):
    """Arena content a masked column must not let through: every page
    holds something, the garbage page too."""
    if leaf.dtype == jnp.int8:
        return jax.random.randint(key, leaf.shape, -127, 128, jnp.int8)
    return (jax.random.normal(key, leaf.shape, jnp.float32)
            * (0.02 if leaf.dtype == jnp.float32 else 1.0)
            ).astype(leaf.dtype)


def _arena(cfg, dtype, pages, seed=0):
    arena = gen.alloc_kv_caches(cfg, pages, PS, dtype)
    leaves, tree = jax.tree_util.tree_flatten(arena)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [_noise(leaf, k) for leaf, k in zip(leaves, keys)])


class _Steps:
    """One net's paged decode step compiled twice for a ``[rows,
    pages]`` table: as the program has it, and with the ladder forced
    to its last rung."""

    def __init__(self, make, dtype, rows=ROWS, pages=PAGES):
        paddle.seed(0)
        self.net = make()
        self.net.eval()
        self.rows, self.pages = rows, pages
        self.arena = _arena(self.net.config, dtype, rows * pages + 1)
        self.table = 1 + np.arange(rows * pages, dtype=np.int32).reshape(
            rows, pages)
        args = (jnp.zeros((rows, 1), jnp.int32), self.arena,
                jnp.zeros((rows,), jnp.int32), jnp.asarray(self.table))
        self.laddered = jax.jit(self._step).lower(*args).compile()
        # two rungs, both the whole table: the forced program keeps its
        # ``case`` (XLA's CPU backend rounds an attention it can fuse
        # with the layer around it otherwise than one behind a branch,
        # at any width: that is no matter of the span)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(qkv, "span_ladder", lambda width: (width, width))
            self.forced = jax.jit(self._step).lower(*args).compile()

    def _step(self, tok, arena, pos, table):
        return gen.decode_step(self.net, tok, arena, pos, page_table=table)

    def both(self, pos, table=None):
        table = self.table if table is None else table
        tok = (7 + 13 * np.arange(self.rows, dtype=np.int32))[:, None]
        args = (jnp.asarray(tok), self.arena,
                jnp.asarray(pos, jnp.int32), jnp.asarray(table))
        return self.laddered(*args), self.forced(*args)


@pytest.fixture(scope="module")
def steps():
    made = {}

    def get(name, **kw):
        key = (name,) + tuple(sorted(kw.items()))
        if key not in made:
            made[key] = _Steps(*NETS[name], **kw)
        return made[key]

    return get


def _assert_bitwise(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _where(rung, place):
    """Position of the longest row: on rung ``rung``'s first column,
    its last, or one past it (the next rung's first)."""
    first = 0 if rung == 0 else LADDER[rung - 1] * PS
    return {"first": first, "last": LADDER[rung] * PS - 1,
            "past": LADDER[rung] * PS}[place]


CASES = [(r, place) for r in range(len(LADDER))
         for place in ("first", "last", "past")
         if not (place == "past" and r == len(LADDER) - 1)]


@pytest.mark.parametrize("rung,place", CASES,
                         ids=[f"rung{r}-{p}" for r, p in CASES])
@pytest.mark.parametrize("name", list(NETS))
def test_every_rung_reads_what_the_whole_table_reads(steps, name, rung,
                                                     place):
    """Logits and written pages, bitwise, with the longest row on the
    rung's first column, its last, and one past it; the other rows
    shorter, one of them free (a zeroed table row at ``pos`` 0)."""
    s = steps(name)
    longest = _where(rung, place)
    pos = np.array([longest // 2, longest, 0, min(longest, 1)], np.int32)
    table = s.table.copy()
    table[2] = 0
    want_rung = rung + (place == "past")
    assert int(qkv.span_rung(PAGES, pos, PS)) == want_rung
    (logits, pages), (logits_f, pages_f) = s.both(pos, table)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    _assert_bitwise(logits, logits_f)
    _assert_bitwise(pages, pages_f)


@pytest.mark.parametrize("pos", [0, 5, LADDER[3] * PS - 1, LADDER[3] * PS,
                                 PAGES * PS - 1])
@pytest.mark.parametrize("name", list(NETS))
def test_one_row_alone(steps, name, pos):
    s = steps(name, rows=1)
    (logits, pages), (logits_f, pages_f) = s.both(np.array([pos]))
    _assert_bitwise(logits, logits_f)
    _assert_bitwise(pages, pages_f)


@pytest.mark.parametrize("name", list(NETS))
def test_every_row_free(steps, name):
    """Nothing admitted yet (the warm-up's launch): all of the table
    zeroed, every row writes and reads the garbage page on rung 0."""
    s = steps(name)
    (logits, pages), (logits_f, pages_f) = s.both(
        np.zeros((ROWS,), np.int32), np.zeros_like(s.table))
    _assert_bitwise(logits, logits_f)
    _assert_bitwise(pages, pages_f)


@pytest.mark.parametrize("name", ["llama_gqa-bf16", "xing4-bf16"])
def test_a_rung_leaves_the_pages_past_it_alone(steps, name):
    """What bounds the read is the rung and not the mask: with NaN in
    every page the table names past rung 1, a batch that fits rung 1
    decodes as if they were clean, while the whole-table read meets
    them behind its mask (NaN - inf is NaN) and is lost."""
    s = steps(name)
    past = s.table[:, LADDER[1]:].ravel()
    clean = s.arena
    s.arena = jax.tree_util.tree_map(
        lambda a: a.at[past].set(jnp.nan), clean)
    try:
        pos = np.array([LADDER[1] * PS - 1, 3, 0, LADDER[0] * PS],
                       np.int32)
        (logits, _), (logits_f, _) = s.both(pos)
    finally:
        s.arena = clean
    (want, _), _ = s.both(pos)
    _assert_bitwise(logits, want)
    assert np.isnan(np.asarray(logits_f, np.float32)).all()


@pytest.mark.parametrize("width,rungs", [
    (4, [1, 2, 3, 4]),
    (16, [2, 4, 6, 8, 10, 12, 14, 16]),
    (256, list(range(32, 257, 32))),     # 4096 / 16: rungs of 512 tokens
    (512, list(range(64, 513, 64))),     # 8192 / 16: rungs of 1024
    (1, [1]),
    (10, [2, 3, 4, 5, 7, 8, 9, 10]),
])
def test_ladder_is_eighths_of_the_table(width, rungs):
    assert qkv.span_ladder(width) == tuple(rungs)


@pytest.mark.parametrize("width,n", [(4, 4), (16, 8)])
def test_program_holds_one_case_a_layer_with_a_branch_a_rung(width, n):
    import re

    paddle.seed(0)
    net = _llama()
    net.eval()
    arena = gen.alloc_kv_caches(net.config, 2 * width + 1, PS, "bfloat16")
    text = jax.jit(lambda t, a, p, tb: gen.decode_step(
        net, t, a, p, page_table=tb)).lower(
        jnp.zeros((2, 1), jnp.int32), arena, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, width), jnp.int32)).as_text()
    cases = re.findall(r'"stablehlo\.case"|stablehlo\.case', text)
    assert len(cases) == net.config.num_hidden_layers
    # a branch a rung: each gathers its own number of pages a row
    for pages in qkv.span_ladder(width):
        assert f"tensor<2x{pages * PS}x2x" in text, pages
    assert len(qkv.span_ladder(width)) == n


def test_host_and_device_pick_the_same_rung():
    """The counter's path (numpy ``pos``) and the program's (a traced
    ``jax.numpy`` one under ``jit``) are one function: over every
    position of a 16-page table, and one past it."""
    on_device = jax.jit(lambda pos: qkv.span_rung(PAGES, pos, PS))
    for p in range(PAGES * PS + 1):
        pos = np.array([0, p, p // 3], np.int32)
        host = qkv.span_rung(PAGES, pos, PS)
        want = min(np.searchsorted(LADDER, p // PS + 1), len(LADDER) - 1)
        assert int(host) == int(on_device(jnp.asarray(pos))) == want, p
