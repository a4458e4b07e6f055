"""The chip's compiler, asked without the chip.

Every Pallas kernel in ``paddle_tpu/kernels`` at Llama-2-7B widths
(hidden 4096, 32 heads x 128) and at the shapes the benchmark's cells
run them at, plus the paged decode step program at depth 1, compiled
for a DESCRIBED v5e chip
(``jax.experimental.topologies``): what the compiler refuses here it
refuses on the chip — a block the tiling cannot take, more VMEM than a
kernel may use, a primitive Mosaic does not lower. Interpret-mode tests
cannot see any of that. A compile that passes is not a chip run;
``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture, never while a
module is imported: one process at a time may load the TPU's library,
and under xdist every worker imports every test file. All of these
tests stay in this one file for the same reason.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import (
    autotune,
    flash_attention as fa,
    rms_norm as rn,
    rope as rp,
)

HID, HEADS, HD = 4096, 32, 128
B, S = 4, 1024          # train rows
ROWS = 8                # decode rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """``compile_(fn, *shapes) -> Compiled``: kernels NOT interpreted,
    persistent compile cache off (an entry written for a described chip
    cannot be read back without one), shapes placed on the described
    chip. ``sds(shape, dtype)`` builds such a shape."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(autotune, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest turns x64 on for the finite-difference harness; the
    # program runs without it, and with it a kernel's index maps come
    # out i64, which Mosaic does not take
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *shapes):
        return jax.jit(fn).lower(*shapes).compile()

    compile_.sds = sds
    yield compile_
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _grad(fn, argnums):
    return jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=argnums)


def _rms(x, w):
    return rn.rms_norm_fused(x, w, 1e-6)


def _flash(q, k, v):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention,
    )

    seq = q.shape[2]
    return flash_attention(
        q, k, v, causal=True, sm_scale=HD ** -0.5,
        block_sizes=fa._tuned_block_sizes(seq, seq))


def _flash_entry(q, k, v):
    """The models' own entry, ``[B, S, H, D]``: selection, the
    transposes and the kernel (the test shows it the chip)."""
    return fa.flash_attention_fwd(q, k, v, causal=True)


def _rope_tables(rows, s):
    return [((rows, s, 1, HD // 2), F32)] * 2


# name -> (function, [(shape, dtype), ...]). Shapes only: nothing here
# touches a device or the topology while the module is imported.
BF, F32 = jnp.bfloat16, jnp.float32
TRAIN, DEC = (B, S, HID), (ROWS, 1, HID)
QKV = [((B, S, HEADS, HD), BF)] * 3
TABLE = [((1, S, 1, HD // 2), F32)] * 2
FLASH = [((2, HEADS, 2048, HD), BF)] * 3
KERNEL_CASES = {
    "rms_norm fwd train": (_rms, [(TRAIN, BF), ((HID,), F32)]),
    "rms_norm bwd train": (_grad(_rms, (0, 1)),
                           [(TRAIN, BF), ((HID,), F32)]),
    "rms_norm bwd train fp32": (_grad(_rms, (0, 1)),
                                [(TRAIN, F32), ((HID,), F32)]),
    "rms_norm fwd+bwd decode": (_grad(_rms, (0, 1)),
                                [(DEC, BF), ((HID,), BF)]),
    # a 338-token prompt: no multiple-of-8 divisor (2 x 169)
    "rms_norm fwd+bwd 338 rows": (
        _grad(_rms, (0, 1)), [((1, 338, HID), BF), ((HID,), BF)]),
    "rope fwd 338 rows": (
        rp.rope_fused, [((1, 338, HEADS, HD), BF),
                        *[((1, 338, 1, HD // 2), F32)] * 2]),
    "rope fwd": (rp.rope_fused, [QKV[0], *TABLE]),
    "rope bwd": (_grad(rp.rope_fused, 0), [QKV[0], *TABLE]),
    "rope per-row decode": (
        rp.rope_fused, [((ROWS, 1, HEADS, HD), BF),
                        *[((ROWS, 1, 1, HD // 2), F32)] * 2]),
    "flash fwd S=2048": (_flash, FLASH),
    "flash bwd S=2048": (_grad(_flash, (0, 1, 2)), FLASH),
    # -- the shapes the benchmark's cells run (BENCHMARK.json): xing4
    # serving (hidden 3584, latent norms 768 and 512 wide; 32 decode
    # rows, a 4096-token prefill whose flash heads are zero-padded to
    # 256), Mistral-7B training (B=2 x S=2048, 32 heads over 8 KV heads)
    "rms_norm fwd xing4 decode": (_rms, [((32, 1, 3584), BF),
                                         ((3584,), BF)]),
    "rms_norm fwd xing4 prefill": (_rms, [((1, 4096, 3584), BF),
                                          ((3584,), BF)]),
    "rms_norm fwd xing4 q latent": (_rms, [((1, 4096, 768), BF),
                                           ((768,), BF)]),
    "rms_norm fwd xing4 kv latent": (_rms, [((32, 1, 512), BF),
                                            ((512,), BF)]),
    "rms_norm fwd+bwd mistral train": (
        _grad(_rms, (0, 1)), [((2, 2048, HID), BF), ((HID,), F32)]),
    "flash fwd xing4 prefill": (
        _flash_entry, [((1, 4096, HEADS, 256), BF)] * 3),
    "flash fwd+bwd mistral train": (
        _grad(_flash_entry, (0, 1, 2)), [((2, 2048, HEADS, HD), BF)] * 3),
    "rope fwd+bwd mistral train q": (
        _grad(rp.rope_fused, 0),
        [((2, 2048, HEADS, HD), BF), *_rope_tables(1, 2048)]),
    "rope fwd+bwd mistral train k": (
        _grad(rp.rope_fused, 0),
        [((2, 2048, 8, HD), BF), *_rope_tables(1, 2048)]),
    "rope per-row decode 32 rows": (
        rp.rope_fused, [((32, 1, HEADS, HD), BF), *_rope_tables(32, 1)]),
}


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_kernel_compiles_for_v5e_at_7b_widths(chip_compile, topo,
                                              monkeypatch, name):
    fn, shapes = KERNEL_CASES[name]
    # flash's selection asks jax.devices() what it runs on
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    compiled = chip_compile(fn, *(chip_compile.sds(*s) for s in shapes))
    assert "tpu_custom_call" in compiled.as_text(), (
        "no Mosaic kernel in the compiled program")


def _kda_step(q, k, v, g, beta, state):
    from paddle_tpu.models import solar_open2

    return solar_open2.kda_step(q, k, v, g, beta, state)


def _kda_scan(q, k, v, g, beta, state):
    from paddle_tpu.models import solar_open2

    return solar_open2.kda_scan(q, k, v, g, beta, state, 64,
                                length=jnp.int32(1500))


def _assert_no_pairwise_tensor(fn, shapes):
    """The chunked scan as lowered forms no ``[.., H, 64, 64, 128]``
    float32 array: the decays of every pair of a chunk's tokens, a
    channel each, which the scan made a chunk at a time before its
    chunks were cut into sub-blocks of 16. All that is formed pair by
    pair now is the four diagonal blocks, ``[16, 16, 128, 4, L]`` with
    the blocks of a group's ``L`` chunk-heads laid minor."""
    heads = shapes[0][0][2]
    text = jax.jit(fn).lower(
        *(jax.ShapeDtypeStruct(*s) for s in shapes)).as_text()
    assert "<16x16x128x4x256xf32>" in text
    assert f"x{heads}x64x64x128xf32" not in text


def _held_experts(h, idx, w, gate_up, down):
    from paddle_tpu.models import xing4

    return xing4.moe_dispatch(h, idx, w, gate_up, down, first=0, held=40)


# Solar-Open2's serving cell (BENCHMARK.json): 64 decode rows and one
# 2048-token prefill of 64 KDA heads of 128 with a float32 state a row,
# 40 of 320 experts of width 1280 held, top-8. No Mosaic kernel of the
# repo's among them: what is held here is that the chip's compiler
# takes the scan's chunk-local part made for every chunk at once and
# the held share's row ladder (three branches of two grouped matmuls
# each), and how much each needs beside its arguments.
_ROW = lambda n, *d: ((n, 64) + d, F32)
SOLAR_CASES = {
    "kda step 64 rows": (
        _kda_step, [_ROW(64, 128)] * 4 + [_ROW(64), _ROW(64, 128, 128)],
        2 << 30),
    "kda chunked scan 2048 tokens": (
        _kda_scan, [((1, 2048, 64, 128), F32)] * 4 + [((1, 2048, 64), F32),
                                                      _ROW(1, 128, 128)],
        2 << 30),
    "held experts decode 64 rows": (
        _held_experts, [((64, 4096), BF), ((64, 8), jnp.int32),
                        ((64, 8), F32), ((40, 4096, 2560), BF),
                        ((40, 1280, 4096), BF)], 1 << 30),
    "held experts prefill 2048 rows": (
        _held_experts, [((2048, 4096), BF), ((2048, 8), jnp.int32),
                        ((2048, 8), F32), ((40, 4096, 2560), BF),
                        ((40, 1280, 4096), BF)], 2 << 30),
}


@pytest.mark.parametrize("name", SOLAR_CASES)
def test_row_state_and_share_programs_compile_for_v5e(chip_compile, name):
    fn, shapes, room = SOLAR_CASES[name]
    compiled = chip_compile(fn, *(chip_compile.sds(*s) for s in shapes))
    assert compiled.memory_analysis().temp_size_in_bytes < room
    if fn is _kda_scan:
        _assert_no_pairwise_tensor(fn, shapes)
    if fn is not _held_experts:
        return
    # the grouped matmuls (the chip's compiler names them
    # ``ragged-dot-none``; the benchmark's readers count them by that
    # name) run at every rung of the row ladder, the first a quarter of
    # the T x 8 sorted rows ...
    from paddle_tpu.models import xing4

    text = compiled.as_text()
    for rows in xing4.row_ladder(shapes[0][0][0] * 8):
        for width in (2560, 4096):
            assert re.search(
                rf"%ragged-dot-none\S* = bf16\[{rows},{width}\]", text), (
                    rows, width)
    # ... and the conditional hands each branch the expert stacks as
    # they lie: nothing of their shape but the program's parameters and
    # the branches' reads of their operand tuple, no copy, no fusion
    made = re.findall(
        r"= bf16\[40,(?:4096,2560|1280,4096)\]\S* ([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "get-tuple-element"}, made


def _held_half(h, idx, w, gate_up, down):
    from paddle_tpu.models import xing4

    return xing4.moe_dispatch(h, idx, w, gate_up, down, first=0, held=128)


def _nope_mla_step(q, ckv, k_pe, w_kvb, arena, pos, table):
    from paddle_tpu.models import KimiLinearConfig, xing4

    return xing4.mla_core(q, ckv, k_pe, w_kvb, None, None,
                          cfg=KimiLinearConfig(), cache=arena, pos=pos,
                          page_table=table)


def _nope_mla_prefill(q, ckv, k_pe, w_kvb, block):
    from paddle_tpu.models import KimiLinearConfig, xing4

    return xing4.mla_core(q, ckv, k_pe, w_kvb, None, None,
                          cfg=KimiLinearConfig(), cache=block,
                          pos=jnp.int32(0))


def _kda_scan_16k(q, k, v, g, beta, state):
    from paddle_tpu.models import solar_open2

    return solar_open2.kda_scan(q, k, v, g, beta, state, 64,
                                length=jnp.int32(12345))


# Kimi-Linear's serving cell (BENCHMARK.json): 32 decode rows whose
# page table is 1152 wide (max_seq_len 18432) and one 16384-token
# prefill; 32 KDA heads of 128, NoPE MLA of 32 heads over a 640-wide
# latent page, 128 of 256 experts of width 1024 held, top-8: the local
# assignments of a step (about 128 of 256 sorted rows) lie on the row
# ladder's first boundary.
_KROW = lambda n, *d: ((n, 32) + d, F32)
_KSTACKS = [((128, 2304, 2048), BF), ((128, 1024, 2304), BF)]
KIMI_CASES = {
    "kda step 32 rows": (
        _kda_step, [_KROW(32, 128)] * 4 + [_KROW(32), _KROW(32, 128, 128)],
        1 << 30),
    "kda chunked scan 16384 tokens": (
        _kda_scan_16k, [((1, 16384, 32, 128), F32)] * 4
        + [((1, 16384, 32), F32), _KROW(1, 128, 128)], 3 << 30),
    "held half decode 32 rows": (
        _held_half, [((32, 2304), BF), ((32, 8), jnp.int32),
                     ((32, 8), F32)] + _KSTACKS, 1 << 30),
    "held half prefill 16384 rows": (
        _held_half, [((16384, 2304), BF), ((16384, 8), jnp.int32),
                     ((16384, 8), F32)] + _KSTACKS, 3 << 30),
    "nope mla absorbed step over a 1152-page table": (
        _nope_mla_step, [((32, 1, 32, 192), BF), ((32, 1, 512), BF),
                         ((32, 1, 64), BF), ((512, 32 * 256), BF),
                         ((32 * 1152 + 1, 16, 640), BF), ((32,), jnp.int32),
                         ((32, 1152), jnp.int32)], 2 << 30),
    "nope mla materialised prefill 16384 tokens": (
        _nope_mla_prefill, [((1, 16384, 32, 192), BF), ((1, 16384, 512), BF),
                            ((1, 16384, 64), BF), ((512, 32 * 256), BF),
                            ((1, 16384, 640), BF)], 3 << 30),
}


@pytest.mark.parametrize("name", KIMI_CASES)
def test_latent_page_beside_row_state_programs_compile_for_v5e(
        chip_compile, topo, monkeypatch, name):
    fn, shapes, room = KIMI_CASES[name]
    # the prefill's flash selection asks jax.devices() what it runs on
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    compiled = chip_compile(fn, *(chip_compile.sds(*s) for s in shapes))
    monkeypatch.undo()
    assert compiled.memory_analysis().temp_size_in_bytes < room
    text = compiled.as_text()
    if fn is _nope_mla_prefill:
        assert "tpu_custom_call" in text     # flash, heads padded to 256
    if fn is _kda_scan_16k:
        _assert_no_pairwise_tensor(fn, shapes)
    if fn is not _held_half:
        return
    from paddle_tpu.models import xing4

    ladder = xing4.row_ladder(shapes[0][0][0] * 8)
    assert ladder == ((128, 256) if shapes[0][0][0] == 32
                      else (32768, 65536, 131072))
    for rows in ladder:
        for width in (2048, 2304):
            assert re.search(
                rf"%ragged-dot-none\S* = bf16\[{rows},{width}\]", text), (
                    rows, width)
    made = re.findall(
        r"= bf16\[128,(?:2304,2048|1024,2304)\]\S* ([\w-]+)\(", text)
    assert made and set(made) <= {"parameter", "get-tuple-element"}, made


def test_rms_norm_row_block_fits_vmem_budget():
    """The row block shrinks with hidden x itemsize, fwd and bwd apart
    (the seed's fixed 256 rows ran the 4096-wide backward out of VMEM),
    and is a multiple of 8 or the whole axis at ANY row count (the
    seed tiled 338 rows by 2, which the chip refuses)."""
    assert rn._block_rows(4096, 4096, 2, n_io=2, n_tmp=1) == 256
    assert rn._block_rows(4096, 4096, 2, n_io=3, n_tmp=4) == 64
    assert rn._block_rows(4096, 4096, 4, n_io=3, n_tmp=4) == 64
    assert rn._block_rows(4096, 2048, 2, n_io=3, n_tmp=4) == 128
    assert rn._block_rows(8, 4096, 2, n_io=3, n_tmp=4) == 8
    assert rn._block_rows(5, 4096, 2, n_io=3, n_tmp=4) == 5
    assert rn._block_rows(12, 4096, 2, n_io=3, n_tmp=4) == 8
    assert rn._block_rows(338, 4096, 2, n_io=3, n_tmp=4) == 64


@pytest.mark.parametrize("rows", [5, 12, 338])
def test_rms_norm_overhanging_last_block_is_masked(rows):
    """Interpreted, on the CPU: a row count its block does not divide
    gives the reference's y, dx and — the rows past the end masked out
    of the accumulation — dw."""
    rng = np.random.RandomState(rows)
    x = jnp.asarray(rng.randn(rows, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64), jnp.float32)

    def ref(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    np.testing.assert_allclose(rn.rms_norm_fused(x, w, 1e-6), ref(x, w),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda x, w: jnp.sin(rn.rms_norm_fused(x, w, 1e-6)).sum(),
                   (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sin(ref(x, w)).sum(), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_paged_decode_step_compiles_at_depth_1(chip_compile, topo,
                                               monkeypatch):
    """The whole decode program ``PagedServingEngine`` serves with, at
    7B widths and depth 1 (bf16 weights as abstract shapes, bf16 pages):
    the default-path kernels are in it and it fits the chip."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedServingEngine

    cfg = paddle.models.LlamaConfig.llama2_7b(
        num_hidden_layers=1, max_position_embeddings=512)
    with paddle.LazyGuard():
        net = paddle.models.LlamaForCausalLM(cfg)
    for p in net.parameters():
        p.value = jax.ShapeDtypeStruct(p.value.shape, jnp.bfloat16)
    net.eval()
    engine = PagedServingEngine(net, max_batch_size=ROWS, max_seq_len=512,
                                page_size=16, min_bucket=64)
    args = jax.tree_util.tree_map(
        lambda a: chip_compile.sds(jnp.shape(a), jnp.result_type(a)),
        engine._decode_example_args())
    # the model picks its kernels from jax.devices(): show it the chip
    # for the length of the trace
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    compiled = engine._decode_fn.lower(*args).compile()
    monkeypatch.undo()
    text = compiled.as_text()
    # rms_norm x3 (two per layer + final) and rope (q, k)
    assert text.count("tpu_custom_call") >= 5, text.count("tpu_custom_call")
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 4 << 30, total
    engine.close()


def test_gqa_paged_decode_step_never_repeats_the_cache():
    """On the CPU lowering, no chip described: the paged decode step of
    a GQA model holds no broadcast as large as the table-gathered cache
    times ``rep`` (what ``repeat_interleave`` to ``H`` heads lowers to:
    ``broadcast_in_dim`` to ``[B, S_virt, kvH, rep, D]``). That copy
    was 34-38 ms of an 80 ms decode program on the chip (PERF.md, PR
    27); the grouped contraction reads each KV head once."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedServingEngine

    cfg = paddle.models.LlamaConfig.tiny(num_key_value_heads=2)
    net = paddle.models.LlamaForCausalLM(cfg)
    net.eval()
    rows, s_max = 4, 64
    engine = PagedServingEngine(net, max_batch_size=rows, max_seq_len=s_max,
                                page_size=8, min_bucket=16)
    text = engine._decode_fn.lower(*engine._decode_example_args()).as_text()
    engine.close()
    rep = cfg.num_attention_heads // cfg.kv_heads
    gathered = rows * s_max * cfg.kv_heads * cfg.head_dim
    shapes = re.findall(r"broadcast(?:_in_dim)?\b[^\n]*->\s*tensor<([0-9x]+)x",
                        text)
    assert shapes, "no broadcast found: the lowering's text has changed"
    sizes = [int(np.prod([int(d) for d in sh.split("x")])) for sh in shapes]
    assert max(sizes) < gathered * rep, (
        f"a broadcast of {max(sizes)} elements: the gathered cache "
        f"({gathered}) repeated to the query heads is back")


def test_kernels_give_way_under_a_mesh_loudly(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned"), so with a multi-device mesh
    installed the compiled kernels are refused — counted, in the
    compiler's words — and the interpreted ones (CPU runs) are not."""
    from jax.sharding import Mesh

    from paddle_tpu.parallel import mesh as mesh_mod

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 virtual devices")
    prev = mesh_mod._STATE["mesh"]
    mesh_mod.set_mesh(Mesh(np.array(devs[:2]), ("mp",)))
    try:
        assert not autotune.spmd_refusal("rms_norm")   # interpreted
        monkeypatch.setattr(autotune, "interpret_mode", lambda: False)
        before = dict(autotune.fallback_counter().series())
        with pytest.warns(RuntimeWarning, match="cannot be automatically"):
            autotune.reset_warned()
            assert autotune.spmd_refusal("rms_norm")
        after = autotune.fallback_counter().series()
        key = (("kernel", "rms_norm"), ("reason", "unpartitionable"))
        assert after.get(key, 0) == before.get(key, 0) + 1
        mesh_mod.set_mesh(None)
        assert not autotune.spmd_refusal("rms_norm")   # no mesh
    finally:
        mesh_mod.set_mesh(prev)
