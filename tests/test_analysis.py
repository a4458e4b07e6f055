"""paddle_tpu.analysis — the TPU-graph linter + recompilation guard.

One minimal positive (rule fires) + one negative (clean graph stays
clean) case per rule, a recompile-storm repro the trace guard must
catch, and the repo-wide gate: the tpu_lint CLI must exit 0 against
the checked-in baseline and nonzero on an injected violation.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import analysis, profiler
from paddle_tpu.analysis import LintConfig, Severity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(rep):
    return {f.rule for f in rep}


# --------------------------------------------------------------- fp64-leak
def test_fp64_leak_positive():
    def f(x):
        return x * jnp.asarray(2.0, jnp.float64)

    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.float64), graph="g")
    assert "fp64-leak" in rules_of(rep)
    assert any(f.severity == Severity.ERROR for f in rep)


def test_fp64_leak_negative():
    def f(x):
        return x * 2.0

    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.float32), graph="g")
    assert "fp64-leak" not in rules_of(rep)


# ------------------------------------------------------------- dtype-churn
def test_dtype_churn_positive_roundtrip():
    def f(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16)

    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.bfloat16), graph="g")
    hits = [f for f in rep if f.rule == "dtype-churn"]
    assert hits and "round trip" in hits[0].message


def test_dtype_churn_positive_bulk_upcast():
    cfg = LintConfig(min_upcast_bytes=1024)

    def f(x):
        return (x.astype(jnp.float32) * 2).sum()

    rep = analysis.lint_fn(f, jnp.ones((64, 64), jnp.bfloat16),
                           graph="g", config=cfg)
    assert any(f.rule == "dtype-churn" and "upcast" in f.detail
               for f in rep)


def test_dtype_churn_quant_whitelist_by_function_name():
    """An int8 quant-dequant convert chain issued from a function whose
    name matches the quant pattern is intentional narrow-dtype
    execution, not churn (the PR 9 kernels land with 0 baseline
    growth)."""
    def _quantize_roundtrip(x):
        q = jnp.clip(jnp.round(x / 0.5), -127, 127).astype(jnp.int8)
        return q.astype(jnp.float32) * 0.5

    rep = analysis.lint_fn(_quantize_roundtrip,
                           jnp.ones((4,), jnp.float32), graph="g")
    assert "dtype-churn" not in rules_of(rep)


def test_dtype_churn_quant_whitelist_by_marker():
    """The explicit ``# tpu-lint: quant`` source marker whitelists a
    chain through a quant dtype even in a neutrally-named function."""
    def _helper(x):
        y = x.astype(jnp.int8)
        return y.astype(jnp.float32)  # tpu-lint: quant

    rep = analysis.lint_fn(_helper, jnp.ones((4,), jnp.float32),
                           graph="g")
    assert "dtype-churn" not in rules_of(rep)


def test_dtype_churn_untagged_quant_chain_still_fires():
    """No tag, no mercy: an int8 chain in a neutrally-named function
    without the marker is still reported (it may well be churn)."""
    def _helper(x):
        y = x.astype(jnp.int8)
        return y.astype(jnp.float32)

    rep = analysis.lint_fn(_helper, jnp.ones((4,), jnp.float32),
                           graph="g")
    assert any(f.rule == "dtype-churn" for f in rep)


def test_dtype_churn_wide_chain_in_quant_named_fn_still_fires():
    """The whitelist needs BOTH a quant dtype in the chain and a tag —
    a bf16/f32 round trip does not get a pass just because it lives in
    a quant-named function."""
    def _quantize_helper(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16)

    rep = analysis.lint_fn(_quantize_helper,
                           jnp.ones((4,), jnp.bfloat16), graph="g")
    assert any(f.rule == "dtype-churn" for f in rep)


def test_dtype_churn_negative():
    def f(x):
        return (x.astype(jnp.float32) * 2).astype(jnp.bfloat16)

    # single convert each way with real work between: no chained pair
    # (note: appending .sum() WOULD be churn — jnp reduces bf16 via an
    # f32 accumulator, an immediate f32->bf16->f32 round trip)
    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.bfloat16), graph="g")
    assert "dtype-churn" not in rules_of(rep)


# ----------------------------------------------------------- host-transfer
def test_host_transfer_positive():
    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), x.dtype), x
        )
        return y + 1

    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.float32), graph="g")
    hits = [f for f in rep if f.rule == "host-transfer"]
    assert hits and hits[0].severity == Severity.ERROR


def test_host_transfer_negative():
    def f(x):
        return x + 1

    rep = analysis.lint_fn(f, jnp.ones((4,), jnp.float32), graph="g")
    assert "host-transfer" not in rules_of(rep)


# ----------------------------------------------------------- donation-miss
def test_donation_miss_positive_and_fix():
    cfg = LintConfig(min_donation_bytes=1024)

    def step(p, g):
        return p - 0.1 * g

    big = jnp.ones((64, 64), jnp.float32)
    rep = analysis.lint_fn(step, big, big, graph="opt", config=cfg)
    assert [f.rule for f in rep] == ["donation-miss"]
    assert "arg0" in rep.findings[0].detail
    # donating the state buffer clears the finding (and must not
    # transfer the miss onto the gradient input)
    rep2 = analysis.lint_fn(step, big, big, graph="opt",
                            donate_argnums=(0,), config=cfg)
    assert len(rep2) == 0


def test_donation_miss_negative_small_buffer():
    def step(p, g):
        return p - 0.1 * g

    small = jnp.ones((4,), jnp.float32)
    rep = analysis.lint_fn(step, small, small, graph="opt")
    assert "donation-miss" not in rules_of(rep)


# ----------------------------------------- collective-mesh-mismatch
def test_collective_mesh_mismatch():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices())
    n = len(devs)
    other = Mesh(devs.reshape(n), ("tp",))
    fn = shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=other,
                   in_specs=P("tp"), out_specs=P())
    x = jnp.ones((n,), jnp.float32)
    # positive: installed mesh has no 'tp' axis
    cfg = LintConfig(mesh_axes=("dp",))
    rep = analysis.lint_fn(fn, x, graph="coll", config=cfg)
    hits = [f for f in rep if f.rule == "collective-mesh-mismatch"]
    assert hits and "tp" in hits[0].detail
    # negative: matching axes
    cfg2 = LintConfig(mesh_axes=("tp",))
    rep2 = analysis.lint_fn(fn, x, graph="coll", config=cfg2)
    assert "collective-mesh-mismatch" not in rules_of(rep2)
    # no mesh known at all: the rule cannot judge and stays silent
    cfg3 = LintConfig(mesh_axes=None)
    from paddle_tpu.parallel import mesh as mesh_mod

    if not mesh_mod.mesh_defined():
        rep3 = analysis.lint_fn(fn, x, graph="coll", config=cfg3)
        assert "collective-mesh-mismatch" not in rules_of(rep3)


# ------------------------------------------------------- broadcast-blowup
def test_broadcast_blowup():
    cfg = LintConfig(min_broadcast_bytes=1024, broadcast_ratio=4.0)

    def f(x):
        return jnp.broadcast_to(x[None, :], (256, x.shape[0]))

    rep = analysis.lint_fn(f, jnp.ones((64,), jnp.float32), graph="g",
                           config=cfg)
    assert "broadcast-blowup" in rules_of(rep)
    # scalar fills (jnp.zeros) must NOT trip it — XLA fuses those
    def g():
        return jnp.zeros((256, 64), jnp.float32)

    rep2 = analysis.lint_fn(g, graph="g", config=cfg)
    assert "broadcast-blowup" not in rules_of(rep2)


# --------------------------------------------------------- recompile storm
def test_trace_guard_storm_repro():
    """Same fn, drifting shapes — the exact failure mode serving's
    bucketing prevents. The guard must flag it; bucketed shapes must
    not."""
    guard = analysis.TraceGuard(max_compiles=4)
    fired = []
    guard.on_fire(fired.append)
    f = jax.jit(lambda x: x * 2)
    guard.watch("decode", f)
    for n in range(1, 8):  # 7 distinct shapes: a storm
        f(jnp.ones((n,), jnp.float32))
    findings = guard.check()
    assert findings and findings[0].rule == "recompile-storm"
    assert fired and fired[0].rule == "recompile-storm"
    assert "decode" in fired[0].message
    # negative: bucketed shapes reuse entries, no storm
    guard2 = analysis.TraceGuard(max_compiles=4)
    g = jax.jit(lambda x: x * 2)
    guard2.watch("bucketed", g)
    for n in (8, 16, 8, 16, 8):
        g(jnp.ones((n,), jnp.float32))
    assert guard2.check() == []


def test_trace_guard_warm_watch_is_not_a_storm():
    """Compiles that happened BEFORE watch() are not this guard's
    storms: growth is measured against the watch-time baseline, and
    reset() re-baselines."""
    f = jax.jit(lambda x: x * 2)
    for n in range(1, 7):  # warm the cache with 6 signatures
        f(jnp.ones((n,), jnp.float32))
    guard = analysis.TraceGuard(max_compiles=4)
    guard.watch("warm", f)
    assert guard.check() == []  # zero growth since watch
    assert guard.compile_counts()["warm"] == 0
    for n in range(7, 13):  # 6 NEW signatures: now a storm
        f(jnp.ones((n,), jnp.float32))
    assert [x.rule for x in guard.check()] == ["recompile-storm"]
    guard.reset()
    assert guard.check() == []  # re-baselined: quiet again


def test_trace_guard_explicit_record():
    guard = analysis.TraceGuard(max_compiles=2)
    assert guard.record_compile("gen", (1, 8)) is None
    assert guard.record_compile("gen", (1, 8)) is None  # hit, not a miss
    assert guard.record_compile("gen", (1, 16)) is None
    f = guard.record_compile("gen", (1, 24))
    assert f is not None and f.rule == "recompile-storm"
    # fires once per key, not per subsequent miss
    assert guard.record_compile("gen", (1, 32)) is None
    assert guard.compile_counts()["gen"] == 4


def test_profiler_surfaces_guard_events():
    profiler.reset_profiler_data()
    guard = analysis.TraceGuard(max_compiles=1)
    guard.record_compile("fn", "a")
    guard.record_compile("fn", "b")
    counts = profiler.lint_event_counts()
    assert any("recompile-storm" in k for k in counts)
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    # events land in summary even when recorded outside the window
    guard2 = analysis.TraceGuard(max_compiles=1)
    guard2.record_compile("fn2", "a")
    guard2.record_compile("fn2", "b")
    text = prof.summary()
    prof.stop()
    assert "recompile-storm" in text


# ----------------------------------------------------------- leaked tracer
def test_leaked_tracer_detection():
    leak = {}

    def f(x):
        leak["t"] = x * 2  # tracer escapes the trace
        return x + 1

    jax.make_jaxpr(f)(jnp.ones((2,)))
    rep = analysis.lint_leaked_tracers(leak, graph="g")
    assert [f.rule for f in rep] == ["leaked-tracer"]
    assert analysis.find_leaked_tracers({"ok": jnp.ones(2)}) == []
    leak.clear()


# ----------------------------------------------------------------- AST lint
AST_CASES = [
    # (rule, positive source, negative source)
    ("traced-branch",
     "import jax\n@jax.jit\ndef f(x):\n    if x > 0:\n        x = -x\n"
     "    return x\n",
     "import jax\n@jax.jit\ndef f(x):\n    if x.shape[0] > 0:\n"
     "        x = -x\n    return x\n"),
    ("host-sync-in-jit",
     "import jax\n@jax.jit\ndef f(x):\n    return float(x) + 1\n",
     "import jax\ndef f(x):\n    return float(x) + 1\n"),
    ("missing-static-argnums",
     "import jax\n@jax.jit\ndef f(x, n):\n    for _ in range(n):\n"
     "        x = x + 1\n    return x\n",
     "import jax, functools\n"
     "@functools.partial(jax.jit, static_argnums=(1,))\n"
     "def f(x, n):\n    for _ in range(n):\n        x = x + 1\n"
     "    return x\n"),
]


@pytest.mark.parametrize("rule,pos,neg", AST_CASES,
                         ids=[c[0] for c in AST_CASES])
def test_ast_rule(rule, pos, neg):
    assert rule in rules_of(analysis.lint_source(pos, "demo.py"))
    assert rule not in rules_of(analysis.lint_source(neg, "demo.py"))


def test_ast_methods_and_sync_calls():
    # the separating statement matters: a disable comment suppresses its
    # own line AND the next line (comment-above style)
    src = (
        "import jax\n@jax.jit\ndef f(x):\n"
        "    y = x.numpy()  # tpu-lint: disable=host-sync-in-jit\n"
        "    y = y + 1\n"
        "    z = x.item()\n"
        "    return z\n"
    )
    rep = analysis.lint_source(src, "demo.py")
    hits = [f for f in rep if f.rule == "host-sync-in-jit"]
    # .numpy() suppressed inline; .item() still caught
    assert len(hits) == 1 and "item" in hits[0].detail


def test_ast_module_level_jit_assignment():
    src = (
        "import jax\n"
        "def f(x, flag):\n"
        "    if flag:\n        return x\n    return -x\n"
        "g = jax.jit(f)\n"
    )
    assert "traced-branch" in rules_of(analysis.lint_source(src, "m.py"))


def test_ast_is_none_and_isinstance_are_static():
    src = (
        "import jax\n@jax.jit\ndef f(x, m):\n"
        "    if m is None:\n        return x\n"
        "    if isinstance(m, tuple):\n        return x\n"
        "    if len(m) > 2:\n        return x\n"
        "    return x + 1\n"
    )
    assert rules_of(analysis.lint_source(src, "m.py")) == set()


# ------------------------------------------------------------- baseline
def test_baseline_roundtrip_and_diff(tmp_path):
    from paddle_tpu.analysis import (
        diff_against_baseline, load_baseline, save_baseline,
    )
    from paddle_tpu.analysis.findings import Finding, Report

    f1 = Finding(rule="fp64-leak", severity="error", message="m",
                 graph="g", detail="mul:float64")
    f2 = Finding(rule="dtype-churn", severity="warning", message="m",
                 graph="g", detail="a->b->a")
    path = str(tmp_path / "base.json")
    save_baseline(path, Report([f1]), notes={f1.key(): "known"},
                  extra_entries=[{"key": "fixed|x", "why": "fixed"}])
    keys, entries = load_baseline(path)
    assert keys == {f1.key()}  # fixed| entries documented, not matched
    assert len(entries) == 2
    new, stale = diff_against_baseline(Report([f1, f2]), keys)
    assert [f.rule for f in new] == ["dtype-churn"] and stale == []
    new2, stale2 = diff_against_baseline(Report([f2]), keys)
    assert len(new2) == 1 and stale2 == [f1.key()]


# -------------------------------------------------------- serving guard
def test_serving_engine_guard_span(monkeypatch):
    """Satellite: when the engine's trace guard fires at runtime the
    recompile shows up via profiler.record_span (chrome traces), not
    only as a silent latency spike."""
    from paddle_tpu.serving.engine import ServingEngine

    spans = []
    import paddle_tpu.serving.engine as eng_mod

    real = profiler.record_span

    def spy(name, dur, kind="user"):
        spans.append((name, kind))
        return real(name, dur, kind=kind)

    monkeypatch.setattr(eng_mod.profiler, "record_span", spy)

    class _Eng(ServingEngine):
        def __init__(self):  # skeleton: only what the guard path needs
            from paddle_tpu.serving.metrics import ServingMetrics

            self.metrics = ServingMetrics()

    e = _Eng()
    guard = analysis.TraceGuard(max_compiles=1)
    guard.on_fire(e._on_guard_fire)
    e.trace_guard = guard
    guard.record_compile("serving::prefill", 8)
    assert spans == []  # under the limit: quiet
    guard.record_compile("serving::prefill", 16)
    assert any(n.startswith("serving::lint_guard::recompile-storm")
               for n, _ in spans)
    assert e.metrics.guard_fires.value == 1


def test_serving_engine_wires_guard():
    from paddle_tpu.serving.engine import ServingEngine

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(3)
    cfg = LlamaConfig.tiny(
        vocab_size=32, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2,
    )
    net = LlamaForCausalLM(cfg)
    net.eval()
    eng = ServingEngine(net, max_batch_size=1, max_seq_len=16,
                        min_bucket=8)
    assert eng.trace_guard is not None
    h = eng.submit(np.array([[1, 2, 3]]), max_new_tokens=2)
    eng.run_until_idle()
    assert h.status is not None
    # one prefill bucket + one adopt bucket recorded, no storm
    counts = eng.trace_guard.compile_counts()
    assert counts.get("serving::prefill") == 1
    assert counts.get("serving::adopt") == 1
    assert eng.trace_guard.findings == []
    eng.close()


# ---------------------------------------------- collective-divergence
def _two_rank_mesh():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:2])
    if len(devs) < 2:
        pytest.skip("needs >= 2 virtual devices")
    return Mesh(devs, ("dp",))


def test_collective_divergence_positive():
    """The distributed-hang shape: one cond branch psums, the other
    does not — ranks disagreeing on the predicate deadlock. jax's own
    varying-axes type check rejects this cond at trace time, so the
    hazard only reaches a compiled program from code that turned that
    check off (``check_vma=False``) — which is where the linter is the
    last line of defence."""
    from jax.sharding import PartitionSpec as P

    mesh = _two_rank_mesh()

    def f(x):
        def body(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v, "dp"),
                lambda v: v,
                x,
            )
        return jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp"), check_vma=False)(x)

    cfg = LintConfig(mesh_axes=("dp",), check_fp64=False)
    rep = analysis.lint_fn(f, jnp.ones((2, 4), jnp.float32),
                           graph="g", config=cfg)
    hits = [f for f in rep if f.rule == "collective-divergence"]
    assert hits and hits[0].severity == Severity.ERROR
    assert "psum" in hits[0].detail


def test_collective_divergence_negative_symmetric_branches():
    """Both branches issue the SAME schedule (different args): every
    rank participates either way — no divergence."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _two_rank_mesh()

    def f(x):
        def body(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v, "dp"),
                lambda v: jax.lax.psum(v * 2, "dp"),
                x,
            )
        return shard_map(body, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    cfg = LintConfig(mesh_axes=("dp",), check_fp64=False)
    rep = analysis.lint_fn(f, jnp.ones((2, 4), jnp.float32),
                           graph="g", config=cfg)
    assert "collective-divergence" not in rules_of(rep)
    # and a collective-free cond stays silent too
    def g(x):
        return jax.lax.cond(x.sum() > 0, lambda v: v + 1,
                            lambda v: v - 1, x)

    rep2 = analysis.lint_fn(g, jnp.ones((4,), jnp.float32), graph="g",
                            config=cfg)
    assert "collective-divergence" not in rules_of(rep2)


def test_collective_divergence_two_rank_vmesh_repro():
    """The real hang shape end-to-end: a TWO-RANK virtual mesh
    subprocess traces a rank-divergent collective branch and the
    linter must flag it (the graph would deadlock if the predicate
    ever split across the ranks)."""
    from tools.vmesh import run_in_virtual_cpu_mesh

    payload = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "from jax.experimental.shard_map import shard_map\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        f"import sys; sys.path.insert(0, {REPO!r})\n"
        "from paddle_tpu import analysis\n"
        "from paddle_tpu.analysis import LintConfig\n"
        "devs = np.array(jax.devices())\n"
        "assert len(devs) == 2, devs\n"
        "mesh = Mesh(devs, ('dp',))\n"
        "def f(x):\n"
        "    def body(x):\n"
        "        # rank-dependent predicate: axis_index differs per\n"
        "        # rank, so rank 0 enters the psum branch alone -> hang\n"
        "        pred = jax.lax.axis_index('dp') == 0\n"
        "        return jax.lax.cond(pred,\n"
        "                            lambda v: jax.lax.psum(v, 'dp'),\n"
        "                            lambda v: v, x)\n"
        "    return shard_map(body, mesh=mesh, in_specs=P('dp'),\n"
        "                     out_specs=P('dp'), check_rep=False)(x)\n"
        "cfg = LintConfig(mesh_axes=('dp',), check_fp64=False)\n"
        "rep = analysis.lint_fn(f, jnp.ones((2, 4), jnp.float32),\n"
        "                       graph='two_rank', config=cfg)\n"
        "rules = sorted({f.rule for f in rep})\n"
        "print('RULES', rules)\n"
    )
    r = run_in_virtual_cpu_mesh(2, payload, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RULES ")][-1]
    assert "collective-divergence" in line, r.stdout


# ------------------------------------------- collective AST rules
def test_rank_conditional_collective_positive():
    src = (
        "import paddle_tpu.distributed as dist\n"
        "def sync(t):\n"
        "    if dist.get_rank() == 0:\n"
        "        dist.all_reduce(t)\n"
    )
    rep = analysis.collective_lint.lint_source(src, "m.py")
    hits = [f for f in rep if f.rule == "rank-conditional-collective"]
    assert hits and hits[0].severity == Severity.ERROR


def test_rank_conditional_collective_negative():
    """Point-to-point under the rank conditional (coordinator idiom),
    symmetric collectives in both branches, and collectives outside
    any rank test all stay clean."""
    src = (
        "import paddle_tpu.distributed as dist\n"
        "def sync(t):\n"
        "    if dist.get_rank() == 0:\n"
        "        dist.send(t, dst=1)\n"
        "    else:\n"
        "        dist.recv(t, src=0)\n"
        "    dist.all_reduce(t)\n"
        "def both(t, rank):\n"
        "    if rank == 0:\n"
        "        dist.broadcast(t, src=0)\n"
        "    else:\n"
        "        dist.broadcast(t, src=0)\n"
    )
    rep = analysis.collective_lint.lint_source(src, "m.py")
    assert "rank-conditional-collective" not in rules_of(rep)


def test_collective_off_main_thread_positive():
    """The PR 5 bug shape: a writer thread's target reaches a
    collective through two call levels."""
    src = (
        "import threading\n"
        "import paddle_tpu.distributed as dist\n"
        "class Saver:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop,\n"
        "                                   daemon=True)\n"
        "    def _loop(self):\n"
        "        self._save()\n"
        "    def _save(self):\n"
        "        dist.barrier()\n"
    )
    rep = analysis.collective_lint.lint_source(src, "m.py")
    hits = [f for f in rep if f.rule == "collective-off-main-thread"]
    assert hits and "barrier" in hits[0].detail
    assert "_loop" in hits[0].detail


def test_collective_off_main_thread_negative():
    """A thread target that only touches host data, with the
    collective on the main path, stays clean."""
    src = (
        "import threading\n"
        "import paddle_tpu.distributed as dist\n"
        "class Saver:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop,\n"
        "                                   daemon=True)\n"
        "    def _loop(self):\n"
        "        self._write()\n"
        "    def _write(self):\n"
        "        open('/tmp/x', 'w').close()\n"
        "    def save(self, t):\n"
        "        dist.all_reduce(t)\n"
    )
    rep = analysis.collective_lint.lint_source(src, "m.py")
    assert "collective-off-main-thread" not in rules_of(rep)


# ------------------------------------------------ concurrency lint
def test_lock_order_inversion_positive():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    hits = [f for f in rep if f.rule == "lock-order-inversion"]
    assert hits and hits[0].severity == Severity.ERROR
    assert "cycle" in hits[0].detail


def test_lock_order_inversion_interprocedural_and_self():
    """One level of call graph: holding A while calling a method that
    takes B conflicts with the direct B->A order. Re-acquiring a
    non-reentrant Lock fires the self: variant; an RLock does not."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "        self._r = threading.RLock()\n"
        "    def takes_b(self):\n"
        "        with self._b:\n"
        "            pass\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            self.takes_b()\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
        "    def re(self):\n"
        "        with self._a:\n"
        "            with self._a:\n"
        "                pass\n"
        "    def re_ok(self):\n"
        "        with self._r:\n"
        "            with self._r:\n"
        "                pass\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    details = {f.detail for f in rep
               if f.rule == "lock-order-inversion"}
    assert any("cycle" in d for d in details), details
    assert "S:self:_a" in details
    assert not any("_r" in d for d in details)


def test_lock_order_inversion_injected_lock_gets_benefit_of_doubt():
    """A `with self.X:` lock with no visible constructor (injected
    from outside) has unknown kind: reentrant nesting must NOT fire
    the self-deadlock variant (it could be an RLock) — but conflicting
    ORDER against another lock still does."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self, lock):\n"
        "        self._ext_lock = lock\n"
        "        self._b = threading.Lock()\n"
        "    def re(self):\n"
        "        with self._ext_lock:\n"
        "            with self._ext_lock:\n"
        "                pass\n"
        "    def one(self):\n"
        "        with self._ext_lock:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._ext_lock:\n"
        "                pass\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    details = {f.detail for f in rep
               if f.rule == "lock-order-inversion"}
    assert not any("self:" in d for d in details), details
    assert any("cycle" in d for d in details), details


def test_lock_order_inversion_negative_consistent_order():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    assert "lock-order-inversion" not in rules_of(rep)


def test_unlocked_shared_write_positive_both_sides():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "    def locked(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
        "    def racy(self):\n"
        "        self.count = 0\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    hits = [f for f in rep if f.rule == "unlocked-shared-write"]
    assert hits and hits[0].detail == "S.count"


def test_unlocked_shared_write_positive_thread_writer():
    """A Thread-target method publishing state without the class's
    lock (the fleet-router health-map shape)."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.status = None\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "    def _loop(self):\n"
        "        self.status = 'alive'\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    hits = [f for f in rep if f.rule == "unlocked-shared-write"]
    assert hits and hits[0].detail == "S.status:thread"


def test_unlocked_shared_write_negative():
    """__init__ writes and consistently-locked writes are clean; a
    class with no locks at all is out of scope."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
        "class NoLocks:\n"
        "    def set(self, v):\n"
        "        self.v = v\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    assert "unlocked-shared-write" not in rules_of(rep)


def test_blocking_call_under_lock_positive():
    src = (
        "import threading, time\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def stop(self, t):\n"
        "        with self._lock:\n"
        "            t.join()\n"
        "    def slow(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    details = {f.detail for f in rep
               if f.rule == "blocking-call-under-lock"}
    assert "S.stop:join" in details
    assert "S.slow:time.sleep" in details


def test_blocking_call_under_lock_interprocedural():
    """One call level: holding the lock while calling a method whose
    body blocks fires too."""
    src = (
        "import threading, time\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def _poll(self):\n"
        "        time.sleep(0.1)\n"
        "    def run(self):\n"
        "        with self._lock:\n"
        "            self._poll()\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    assert any(f.rule == "blocking-call-under-lock"
               and "_poll()" in f.detail for f in rep)


def test_blocking_call_under_lock_negative_condition_wait():
    """Condition.wait releases the lock — the mailbox pattern
    (AsyncSaver) must stay clean, as must blocking calls made with no
    lock held."""
    src = (
        "import threading, time\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._done = threading.Condition(self._lock)\n"
        "    def wait(self):\n"
        "        with self._lock:\n"
        "            self._done.wait()\n"
        "    def outside(self, t):\n"
        "        t.join()\n"
        "        time.sleep(0.1)\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    assert "blocking-call-under-lock" not in rules_of(rep)


def test_concurrency_lint_inline_suppression():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "    def locked(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"
        "    def racy(self):\n"
        "        self.count = 0  # tpu-lint: disable=unlocked-shared-write\n"
    )
    rep = analysis.concurrency_lint.lint_source(src, "m.py")
    assert "unlocked-shared-write" not in rules_of(rep)


# ------------------------------------------------- runtime lock sentinel
def _locked_pair():
    import threading

    class Obj:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

    return Obj()


def test_lock_sentinel_detects_seeded_inversion():
    """Deterministic seeded inversion: thread 1 takes A->B, thread 2
    (strictly after) takes B->A. No deadlock ever happens — the
    sentinel flags the latent one from the order graph alone."""
    import threading

    from paddle_tpu.analysis import lock_sentinel as ls

    sent = ls.LockSentinel()
    o = _locked_pair()
    names = ls.instrument_locks(o, sentinel=sent, name="Obj")
    assert names == ["Obj._a", "Obj._b"]

    def ab():
        with o._a:
            with o._b:
                pass

    def ba():
        with o._b:
            with o._a:
                pass

    t = threading.Thread(target=ab)
    t.start(); t.join()
    assert sent.inversions() == []  # one order seen: no inversion yet
    t = threading.Thread(target=ba)
    t.start(); t.join()
    inv = sent.inversions()
    assert len(inv) == 1 and inv[0].severity == Severity.ERROR
    assert inv[0].detail == "runtime:Obj._a<->Obj._b"
    # fires once per pair, not per repetition
    t = threading.Thread(target=ba)
    t.start(); t.join()
    assert len(sent.inversions()) == 1


def test_lock_sentinel_negative_consistent_order_and_metrics():
    import threading

    from paddle_tpu.analysis import lock_sentinel as ls
    from paddle_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    sent = ls.LockSentinel(registry=reg)
    o = _locked_pair()
    ls.instrument_locks(o, sentinel=sent, name="Obj")

    def ab():
        with o._a:
            with o._b:
                pass

    for _ in range(3):
        t = threading.Thread(target=ab)
        t.start(); t.join()
    assert sent.inversions() == []
    assert sent.edge_count() == 1  # a->b only
    # the instrumented gauge landed in the handed-in registry
    g = reg.get("paddle_analysis_lock_instrumented")
    assert g is not None and g.value() == 2.0


def test_lock_sentinel_long_hold():
    from paddle_tpu.analysis import lock_sentinel as ls
    from paddle_tpu.chaos import ChaosClock

    clk = ChaosClock()
    sent = ls.LockSentinel(long_hold_s=0.5, clock=clk)
    o = _locked_pair()
    ls.instrument_locks(o, sentinel=sent, name="Obj")
    with o._a:
        clk.advance(1.0)
    holds = sent.long_holds()
    assert len(holds) == 1 and "Obj._a" in holds[0].detail
    # quick holds stay quiet
    with o._b:
        clk.advance(0.1)
    assert len(sent.long_holds()) == 1


def test_lock_sentinel_skips_condition_wrapped_locks():
    """AsyncSaver's mailbox lock is captured by two Conditions — the
    sentinel must leave it alone (wrapping would desync Condition.wait
    from the lock object) while the saver keeps working."""
    from paddle_tpu.analysis import lock_sentinel as ls
    from paddle_tpu.checkpoint.async_saver import AsyncSaver

    sent = ls.LockSentinel()
    saver = AsyncSaver()
    try:
        assert ls.instrument_locks(saver, sentinel=sent) == []
        ran = []
        saver.submit(lambda: ran.append(1))
        assert saver.wait(timeout=10) and ran == [1]
    finally:
        saver.close()


def test_lock_sentinel_cross_thread_handoff_release():
    """A Lock acquired on one thread and released on another (legal
    hand-off) must not leave a phantom hold poisoning the acquirer's
    order graph with false inversions."""
    import threading

    from paddle_tpu.analysis import lock_sentinel as ls

    sent = ls.LockSentinel()
    o = _locked_pair()
    ls.instrument_locks(o, sentinel=sent, name="Obj")
    o._a.acquire()  # main thread acquires...

    t = threading.Thread(target=o._a.release)  # ...worker releases
    t.start(); t.join()
    # main thread no longer holds _a: b-then-a on a worker plus plain
    # b and a nestings here must NOT read as an inversion
    with o._b:
        with o._a:
            pass
    t = threading.Thread(target=lambda: o._a.acquire() or o._a.release())
    t.start(); t.join()
    assert sent.inversions() == [], \
        [str(f) for f in sent.inversions()]


def test_lock_sentinel_malformed_threshold_env(monkeypatch):
    """A typo'd PADDLE_TPU_LOCK_LONG_HOLD_S must degrade to the
    default, never crash construction (the process-wide sentinel is
    built at import time)."""
    from paddle_tpu.analysis import lock_sentinel as ls

    monkeypatch.setenv("PADDLE_TPU_LOCK_LONG_HOLD_S", "not-a-number")
    sent = ls.LockSentinel()
    assert sent.long_hold_s == ls.DEFAULT_LONG_HOLD_S


def test_maybe_instrument_env_gated(monkeypatch):
    """The constructor seam: inert by default, wraps the runtime's
    locks when PADDLE_TPU_LOCK_SENTINEL=1."""
    from paddle_tpu.analysis import lock_sentinel as ls
    from paddle_tpu.training import TrainWatchdog

    monkeypatch.delenv("PADDLE_TPU_LOCK_SENTINEL", raising=False)
    wd = TrainWatchdog(stall_seconds=60.0)
    assert not isinstance(wd._lock, ls.SentinelLock)
    monkeypatch.setenv("PADDLE_TPU_LOCK_SENTINEL", "1")
    with ls.use_sentinel(ls.LockSentinel()) as sent:
        wd2 = TrainWatchdog(stall_seconds=60.0)
        assert isinstance(wd2._lock, ls.SentinelLock)
        assert any("TrainWatchdog" in n for n in sent.instrumented)
        wd2.note_dispatch(1)  # the wrapped lock serves the hot path
        assert wd2.check() == []
        assert sent.inversions() == []


# ------------------------------------------------------------ the CLI gate
@pytest.fixture(scope="module")
def lint_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_ENABLE_X64", None)  # lint the production (f32) graphs
    return env


def test_cli_ast_only_exits_zero_on_baseline(lint_env):
    """Fast repo gate: the source tree — including the collective and
    lock-discipline passes — must be clean vs the baseline."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         "--ast-only", "--concurrency", "--json"],
        capture_output=True, text=True, env=lint_env, cwd=REPO,
        timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.loads(out.stdout)
    assert rep["new"] == []
    # the dogfood run carries its accepted concurrency findings (each
    # with a documented why in the baseline) — the passes really ran
    rules = {f["rule"] for f in rep["findings"]}
    assert "collective-off-main-thread" in rules
    assert "unlocked-shared-write" in rules


def test_cli_fails_on_injected_violation(tmp_path, lint_env):
    """The gate must demonstrably fail (nonzero exit, named rule) on an
    injected violation."""
    bad = tmp_path / "paddle_tpu_bad.py"
    bad.write_text(
        "import jax\n\n@jax.jit\ndef decode(x, n):\n"
        "    if x > 0:\n        return x.numpy()\n"
        "    for _ in range(n):\n        x = x + 1\n    return x\n"
    )
    code = (
        "import sys, json\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from paddle_tpu import analysis\n"
        f"rep = analysis.lint_path({str(tmp_path)!r})\n"
        f"keys, _ = analysis.load_baseline("
        f"{os.path.join(REPO, 'tools', 'tpu_lint_baseline.json')!r})\n"
        "new, _ = analysis.diff_against_baseline(rep, keys)\n"
        "print(json.dumps(sorted({f.rule for f in new})))\n"
        "sys.exit(1 if len(new) else 0)\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=lint_env,
                         timeout=300)
    assert out.returncode == 1, out.stdout + out.stderr
    rules = json.loads(out.stdout.strip().splitlines()[-1])
    assert {"traced-branch", "host-sync-in-jit",
            "missing-static-argnums"} <= set(rules)


@pytest.mark.slow
def test_cli_full_graph_gate(lint_env):
    """The full dogfood: trace llama fwd / train step / serving decode /
    optimizer step and gate against the baseline (slow: ~1 min)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py")],
        capture_output=True, text=True, env=lint_env, cwd=REPO,
        timeout=560,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_graph_lint_in_process_on_tiny_graphs():
    """Tier-1-speed version of the dogfood: the pure-jaxpr passes over a
    tiny forward + optimizer update must produce no unbaselined
    findings (x64 CI env: fp64 rule off — conftest enables float64
    globally, which the production CLI env never does)."""
    from paddle_tpu.optimizer.optimizer import _adam_update

    cfg = LintConfig(check_fp64=False, min_donation_bytes=1024)
    p = jnp.ones((64, 64), jnp.float32)
    rep = analysis.lint_fn(
        _adam_update.__wrapped__, p, p, p, p, jnp.float32(1e-3),
        jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-8),
        jnp.float32(1.0), jnp.float32(0.0), False,
        graph="optimizer_step", donate_argnums=(0, 1, 2),
        static_argnums=(10,), config=cfg,
    )
    assert len(rep) == 0, "\n".join(str(f) for f in rep)

    from paddle_tpu.optimizer.optimizer import (
        _adadelta_update, _adamax_update,
    )

    rep2 = analysis.lint_fn(
        _adadelta_update.__wrapped__, p, p, p, p, jnp.float32(1e-3),
        jnp.float32(0.95), jnp.float32(1e-6),
        graph="adadelta_step", donate_argnums=(0, 1, 2), config=cfg,
    )
    assert len(rep2) == 0, "\n".join(str(f) for f in rep2)
    rep3 = analysis.lint_fn(
        _adamax_update.__wrapped__, p, p, p, p, jnp.float32(1e-3),
        jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-8),
        jnp.float32(1.0),
        graph="adamax_step", donate_argnums=(0, 1, 2), config=cfg,
    )
    assert len(rep3) == 0, "\n".join(str(f) for f in rep3)
